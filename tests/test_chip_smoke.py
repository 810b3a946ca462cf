"""chip_smoke.py rehearsed on the CPU at toy size.

The script's sizes and its device gate are module-level names; the tests
steer them by monkeypatching (no option of the script exists for that).
What cannot be rehearsed here — that the chunk program is the TPU one —
is asserted the other way round: on the CPU that check must FAIL, and
``main()`` must end with ``"ok": false`` and a non-zero exit code.
"""

import json
import os
import sys

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

import chip_smoke  # noqa: E402

TOY = {"nsamp": 1 << 16, "nbits": 4, "numdms": 8, "lodm": 62.0,
       "dmstep": 2.0}


@pytest.fixture()
def toy(monkeypatch, tmp_path):
    """Toy depth AND toy widths (the CPU cannot carry 1024 channels in
    a unit test); every array is accepted on the platform JAX has."""
    import jax

    monkeypatch.setattr(chip_smoke, "NCHAN", 64)
    monkeypatch.setattr(chip_smoke, "INJ_PERIOD", 1024)
    monkeypatch.setattr(chip_smoke, "ZMAX", 20)
    monkeypatch.setattr(chip_smoke, "WINDOW", 1 << 14)
    monkeypatch.setattr(chip_smoke, "TWIN_NPART", 8)
    # the floor scales with sqrt(length): 8 at the toy's 2^16 samples
    monkeypatch.setattr(chip_smoke, "SNR_FLOOR", 64.0)
    monkeypatch.setattr(chip_smoke, "SIZES", dict(TOY))
    monkeypatch.setattr(chip_smoke, "SIZES_CHIPS4", dict(TOY))
    monkeypatch.setattr(chip_smoke, "FLEET_NSAMP", 1 << 15)
    # a .so another xdist worker may be loading is not ours to remove
    monkeypatch.setattr(chip_smoke, "NATIVE_LIB",
                        str(tmp_path / "no_such_libpsrcodec.so"))
    here = jax.devices()[0].platform
    monkeypatch.setattr(
        chip_smoke, "_on_tpu",
        lambda *arrays: all(d.platform == here
                            for a in arrays for d in a.devices()))
    return tmp_path


def test_one_chip_phases_at_toy_size(toy, capsys):
    """Every phase function of the default run, in the order main() runs
    them: input from the seed, the chain through the survey entry point,
    recovery, zero fallbacks, the stage twins."""
    sizes = chip_smoke.SIZES
    chip_smoke.print_sizes(sizes, {})
    chip_smoke.phase_native()
    fil = chip_smoke.make_input(str(toy), 0, sizes)
    assert chip_smoke.make_input(str(toy), 0, sizes) == fil  # reused
    stem = os.path.splitext(os.path.basename(fil))[0]
    outdir = str(toy / "out")
    wall = chip_smoke.run_survey([fil], outdir, sizes)
    summ = chip_smoke.telemetry_summary(outdir)
    chip_smoke.print_walls("run", wall, summ)
    chip_smoke.check_recovery(outdir, stem, sizes)
    chip_smoke.check_no_fallback(summ)
    dev = chip_smoke.check_twins(fil, sizes)
    assert set(dev) == {"mask", "sweep", "prep", "accel", "fold"}
    out = capsys.readouterr().out
    assert "reduced: nsamp" in out and "recovered: frequency = injected" in out
    assert "compile.ms" in out and "cache directory" in out


def test_device_path_check_fails_off_the_chip(toy):
    """On the CPU `auto` resolves to the gather engine and the lax boxcar:
    the check that gates the smoke on the TPU program must say so."""
    with pytest.raises(chip_smoke.PhaseFailed, match="not the TPU one"):
        chip_smoke.check_device_path()


def test_fallback_counters_gate(toy):
    class Summ:
        counters = {"fold.numpy_fallbacks": 1}
        events = {}

    with pytest.raises(chip_smoke.PhaseFailed, match="fold.numpy_fallbacks"):
        chip_smoke.check_no_fallback(Summ)
    Summ.counters = {"compile.aot_fallback": 3}
    chip_smoke.check_no_fallback(Summ, gate_compile=False)
    with pytest.raises(chip_smoke.PhaseFailed, match="aot_fallback"):
        chip_smoke.check_no_fallback(Summ)
    Summ.counters, Summ.events = {}, {"survey.stage_retry": 1}
    with pytest.raises(chip_smoke.PhaseFailed, match="stage_retry"):
        chip_smoke.check_no_fallback(Summ)


def test_four_chip_phase_on_virtual_devices(toy, monkeypatch, capsys):
    """`--chips 4` end to end on four virtual CPU devices: the gang of 4
    against the 1-chip run, the per-device roll-up, the sharded
    intermediates, the fleet of four."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices (tests/conftest.py forces 8)")
    args = chip_smoke.parse_args(["--chips", "4", "--fleet", "--workdir",
                                  str(toy)])
    chip_smoke.run_four_chips(args)
    out = capsys.readouterr().out
    assert "per-device roll-up" in out and "byte identity" in out
    assert "sharded intermediates" in out and "on 4 devices" in out
    assert "fleet of 4 on 4 chips" in out
    # the fleet leg reads the per-chip lease counters, and its second pass
    # compiles nothing on any chip
    assert "chips that held a sweep lease [0, 1, 2, 3]" in out
    assert 'second pass: {"compile.cache_miss": 0, "jit.compiles": 0' in out
    assert "check no-compile-fleet4-again: ok" in out


def test_require_chips_refuses_fewer_than_asked(monkeypatch):
    monkeypatch.setattr(chip_smoke, "device_record",
                        lambda: {"platform": "tpu", "kind": "TPU v5 lite",
                                 "count": 1})
    assert chip_smoke.require_chips(1)["count"] == 1
    with pytest.raises(chip_smoke.PhaseFailed, match="4 chips asked"):
        chip_smoke.require_chips(4)


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_on_cpu_says_not_ok_and_exits_nonzero(argv, tmp_path, capsys):
    rc = chip_smoke.main([*argv, "--workdir", str(tmp_path)])
    last = capsys.readouterr().out.strip().splitlines()[-1]
    rec = json.loads(last)
    assert rc != 0 and rec["ok"] is False
    assert rec["device"]["platform"] == "cpu"
    assert set(rec) == {"ok", "device"}
    assert not os.listdir(tmp_path)  # refused before anything was made
