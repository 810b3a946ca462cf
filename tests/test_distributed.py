"""Multi-host path tests (SURVEY.md §2.4 rows 4-5, VERDICT r2 item 6).

Single-process behavior is tested in-process; the real ``jax.distributed``
2-process path runs as a subprocess integration test on the CPU backend
(two ranks join a localhost coordinator, sweep disjoint file shares, and
all-gather the merged candidate table)."""

import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from pypulsar_tpu.ops import numpy_ref
from pypulsar_tpu.parallel import distributed

_MP_PROBE: list = []  # cached (ok, detail) of the capability probe

_PROBE_SCRIPT = textwrap.dedent("""
    import os
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(os.environ["PROBE_COORD"], 2,
                               int(os.environ["PROBE_RANK"]))
    from jax.experimental import multihost_utils
    out = multihost_utils.process_allgather(np.arange(4.0))
    assert np.asarray(out).size == 8
    print("PROBE OK")
""")


def _probe_cpu_collectives():
    """(ok, detail): can this jaxlib run REAL 2-process CPU collectives?
    Some jaxlib builds raise 'Multiprocess computations aren't
    implemented on the CPU backend' from process_allgather — an
    environment capability, not a code bug, so the two-process
    integration tests skip with that reason instead of failing red."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        env["PROBE_COORD"] = f"127.0.0.1:{port}"
        env["PROBE_RANK"] = str(rank)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _PROBE_SCRIPT], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    try:
        outs = [p.communicate(timeout=120) for p in procs]
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        return False, "2-process collective probe timed out"
    for p, (_out, err) in zip(procs, outs):
        if p.returncode != 0:
            tail = err.strip().splitlines()
            return False, (tail[-1][-200:] if tail else "no stderr")
    return True, ""


def _require_cpu_collectives():
    """Runtime capability gate for the two-process integration tests
    (probe runs once per session, only when such a test executes)."""
    if not _MP_PROBE:
        _MP_PROBE.append(_probe_cpu_collectives())
    ok, detail = _MP_PROBE[0]
    if not ok:
        pytest.skip("environment capability: jaxlib CPU backend cannot "
                    f"run 2-process collectives ({detail})")


def test_shard_files_round_robin():
    files = [f"f{i}" for i in range(7)]
    assert distributed.shard_files(files, index=0, count=3) == ["f0", "f3", "f6"]
    assert distributed.shard_files(files, index=2, count=3) == ["f2", "f5"]
    all_shards = [distributed.shard_files(files, index=i, count=3)
                  for i in range(3)]
    assert sorted(sum(all_shards, [])) == sorted(files)


def test_shard_files_surplus_hosts_empty_not_aliased():
    """The round-18 idle-host contract: with more processes than files
    the surplus ranks get clean EMPTY slices (they join the survey
    claim pool as adopters — tests/test_multihost.py pins that side),
    the partition still covers every file exactly once, and an
    out-of-grid rank is a loud error rather than a silent alias of
    another host's share."""
    files = [f"f{i}" for i in range(3)]
    shards = [distributed.shard_files(files, index=i, count=8)
              for i in range(8)]
    assert [s for s in shards[3:] if s] == []  # surplus ranks idle
    assert sorted(sum(shards, [])) == sorted(files)  # no file dropped
    assert all(len(s) <= 1 for s in shards)  # and none double-assigned
    with pytest.raises(ValueError):
        distributed.shard_files(files, index=8, count=8)
    with pytest.raises(ValueError):
        distributed.shard_files(files, index=-1, count=8)
    with pytest.raises(ValueError):
        distributed.shard_files(files, index=0, count=0)


def test_local_rank_env_first(monkeypatch):
    """local_rank/local_count read the launcher env grid without
    touching jax — the path the survey --hosts children derive their
    host ids from."""
    monkeypatch.setenv(distributed.ENV_NPROC, "4")
    monkeypatch.setenv(distributed.ENV_PID, "2")
    assert distributed.local_count() == 4
    assert distributed.local_rank() == 2
    monkeypatch.setenv(distributed.ENV_NPROC, "1")
    assert distributed.local_count() == 1
    assert distributed.local_rank() == 0


def test_initialize_noop_without_coordinator(monkeypatch):
    monkeypatch.delenv(distributed.ENV_COORD, raising=False)
    assert distributed.initialize() is False


def test_allgather_candidates_single_process():
    recs = np.array([[0.0, 60.0, 12.0, 2.0, 100.0],
                     [1.0, 30.0, 8.0, 4.0, 50.0]])
    out = distributed.allgather_candidates(recs, pad_to=4)
    np.testing.assert_array_equal(out, recs)


def _write_fil(path, dm, t0, seed, C=32, T=8192, dt=1e-3):
    from pypulsar_tpu.io import filterbank

    freqs = 1500.0 - 2.0 * np.arange(C)
    rng = np.random.RandomState(seed)
    data = rng.randn(T, C).astype(np.float32)
    bins = numpy_ref.bin_delays(dm, freqs, dt)
    for c in range(C):
        idx = t0 + bins[c]
        if idx < T:
            data[idx, c] += 10.0
    hdr = dict(nchans=C, tsamp=dt, fch1=1500.0, foff=-2.0, tstart=55000.0,
               nbits=32, nifs=1, source_name="DTEST")
    filterbank.write_filterbank(path, hdr, data)


def test_multi_host_sweep_single_process(tmp_path):
    """The multi-host API degenerates correctly to one process."""
    f0 = str(tmp_path / "a.fil")
    f1 = str(tmp_path / "b.fil")
    _write_fil(f0, dm=40.0, t0=2000, seed=0)
    _write_fil(f1, dm=90.0, t0=5000, seed=1)
    dms = np.linspace(0.0, 120.0, 16)
    merged = distributed.multi_host_sweep([f0, f1], dms, nsub=8,
                                          group_size=4, topk_per_file=4)
    assert set(merged[:, 0].astype(int)) == {0, 1}
    best_a = merged[merged[:, 0] == 0][0]
    best_b = merged[merged[:, 0] == 1][0]
    assert abs(best_a[1] - 40.0) <= 16.0
    assert abs(best_b[1] - 90.0) <= 16.0


def test_time_shard_merge_matches_whole_sweep(tmp_path):
    """Two in-process time-shard windows merge to the sequential sweep:
    mb/ab (every peak value and its global sample) bit-identical, SNR
    equal to f64 re-association (the seam contract of the windowed
    _ReaderSource + merge_accum_parts)."""
    from pypulsar_tpu.io import filterbank
    from pypulsar_tpu.parallel.staged import sweep_flat
    from pypulsar_tpu.parallel.sweep import finalize_sweep, merge_accum_parts

    fn = str(tmp_path / "ts.fil")
    _write_fil(fn, dm=60.0, t0=6000, seed=3, T=8192)
    dms = np.linspace(0.0, 100.0, 12)
    whole = sweep_flat(filterbank.FilterbankFile(fn), dms, nsub=8,
                       group_size=4, chunk_payload=2048).steps[0].result

    plan = None
    parts = []
    for rank in (0, 1):
        plan, acc = distributed.time_shard_local_accum(
            fn, dms, rank, 2, nsub=8, group_size=4, chunk_payload=2048)
        parts.append(acc)
    assert parts[0].n + parts[1].n == 8192
    merged = merge_accum_parts(parts)
    res = finalize_sweep(plan, merged.n, merged.s, merged.ss, merged.mb,
                         merged.ab, merged.baseline_sum)
    np.testing.assert_array_equal(res.peak_sample, whole.peak_sample)
    np.testing.assert_allclose(res.snr, whole.snr, rtol=1e-9, atol=1e-9)
    # the recovered injection survives sharding
    best = res.best(1)[0]
    assert abs(best["dm"] - 60.0) <= 10.0 and best["snr"] > 8.0


def test_time_shard_masked_matches_flat(tmp_path):
    """rfimask fill composes with time windows: the masked time-sharded
    merge equals the masked sequential sweep (mask fill is per-block and
    window blocks are the same blocks)."""
    from pypulsar_tpu.io import filterbank
    from pypulsar_tpu.io.rfimask import RfifindMask, write_mask
    from pypulsar_tpu.parallel.staged import sweep_flat
    from pypulsar_tpu.parallel.sweep import finalize_sweep, merge_accum_parts

    fn = str(tmp_path / "tsm.fil")
    _write_fil(fn, dm=60.0, t0=6000, seed=5, T=8192)
    # DIFFERENT channels per interval: a window-relative (instead of
    # file-absolute) interval lookup on rank 1 would fill the wrong
    # channels and fail the parity below
    maskfn = str(tmp_path / "tsm.mask")
    nint = 4
    write_mask(maskfn, nchan=32, nint=nint, ptsperint=8192 // nint,
               zap_chans=np.array([], np.int64),
               zap_ints=np.array([], np.int64),
               zap_chans_per_int=[np.array([3]), np.array([5, 11]),
                                  np.array([7]), np.array([9, 20])])
    mask = RfifindMask(maskfn)

    dms = np.linspace(0.0, 100.0, 12)
    whole = sweep_flat(filterbank.FilterbankFile(fn), dms, nsub=8,
                       group_size=4, chunk_payload=2048,
                       rfimask=mask).steps[0].result
    plan = None
    parts = []
    for rank in (0, 1):
        plan, acc = distributed.time_shard_local_accum(
            fn, dms, rank, 2, nsub=8, group_size=4, chunk_payload=2048,
            rfimask=mask)
        parts.append(acc)
    merged = merge_accum_parts(parts)
    res = finalize_sweep(plan, merged.n, merged.s, merged.ss, merged.mb,
                         merged.ab, merged.baseline_sum)
    np.testing.assert_array_equal(res.peak_sample, whole.peak_sample)
    np.testing.assert_allclose(res.snr, whole.snr, rtol=1e-9, atol=1e-9)


def test_time_shard_downsampled_matches_flat(tmp_path):
    """--downsamp composes with time windows: windows align to whole raw
    bins, so the downsampled shard merge equals the downsampled
    sequential sweep."""
    from pypulsar_tpu.io import filterbank
    from pypulsar_tpu.parallel.staged import sweep_flat
    from pypulsar_tpu.parallel.sweep import finalize_sweep, merge_accum_parts

    fn = str(tmp_path / "tsd.fil")
    _write_fil(fn, dm=60.0, t0=6000, seed=6, T=8192)
    dms = np.linspace(0.0, 100.0, 12)
    whole = sweep_flat(filterbank.FilterbankFile(fn), dms, downsamp=2,
                       nsub=8, group_size=4,
                       chunk_payload=1024).steps[0].result
    plan = None
    parts = []
    for rank in (0, 1):
        plan, acc = distributed.time_shard_local_accum(
            fn, dms, rank, 2, nsub=8, group_size=4, chunk_payload=1024,
            downsamp=2)
        parts.append(acc)
    assert parts[0].n + parts[1].n == 4096  # downsampled sample count
    merged = merge_accum_parts(parts)
    res = finalize_sweep(plan, merged.n, merged.s, merged.ss, merged.mb,
                         merged.ab, merged.baseline_sum)
    np.testing.assert_array_equal(res.peak_sample, whole.peak_sample)
    np.testing.assert_allclose(res.snr, whole.snr, rtol=1e-9, atol=1e-9)


def test_time_shard_single_count_matches_flat(tmp_path):
    """count=1 time_sharded_sweep is exactly sweep_flat (the degenerate
    window is the whole file and no collective runs)."""
    from pypulsar_tpu.io import filterbank
    from pypulsar_tpu.parallel.staged import sweep_flat

    fn = str(tmp_path / "ts1.fil")
    _write_fil(fn, dm=45.0, t0=3000, seed=4, T=4096)
    dms = np.linspace(0.0, 100.0, 8)
    whole = sweep_flat(filterbank.FilterbankFile(fn), dms, nsub=8,
                       group_size=4, chunk_payload=2048).steps[0].result
    res = distributed.time_sharded_sweep(fn, dms, nsub=8, group_size=4,
                                         chunk_payload=2048, rank=0, count=1)
    np.testing.assert_array_equal(res.snr, whole.snr)
    np.testing.assert_array_equal(res.peak_sample, whole.peak_sample)


_RANK_SCRIPT = textwrap.dedent("""
    import os, sys
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, {repo!r})
    from pypulsar_tpu.parallel import distributed

    ok = distributed.initialize()
    assert ok, "distributed.initialize() did not engage"
    assert jax.process_count() == 2
    files = [{f0!r}, {f1!r}]
    dms = np.linspace(0.0, 120.0, 16)
    merged = distributed.multi_host_sweep(files, dms, nsub=8, group_size=4,
                                          topk_per_file=4)
    np.save(os.path.join({out!r}, "merged_rank%d.npy" % jax.process_index()),
            merged)
    print("RANK", jax.process_index(), "OK", len(merged))
""")


_TS_RANK_SCRIPT = textwrap.dedent("""
    import os, sys
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, {repo!r})
    from pypulsar_tpu.parallel import distributed

    ok = distributed.initialize()
    assert ok, "distributed.initialize() did not engage"
    dms = np.linspace(0.0, 100.0, 12)
    res = distributed.time_sharded_sweep({fn!r}, dms, nsub=8, group_size=4,
                                         chunk_payload=2048)
    rank = jax.process_index()
    np.save(os.path.join({out!r}, "ts_snr_rank%d.npy" % rank), res.snr)
    np.save(os.path.join({out!r}, "ts_peak_rank%d.npy" % rank),
            res.peak_sample)
    print("RANK", rank, "OK")
""")


def test_time_sharded_sweep_two_process(tmp_path):
    """Real jax.distributed: 2 CPU ranks each stream HALF of one file's
    time axis (windowed prefetch + seam overlap), all-gather ~KB
    accumulators, and finalize identical SweepResults — the road past a
    per-host wire ceiling (BENCHNOTES r4)."""
    _require_cpu_collectives()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    fn = str(tmp_path / "big.fil")
    _write_fil(fn, dm=60.0, t0=6000, seed=3, T=8192)

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    script = _TS_RANK_SCRIPT.format(repo=repo, fn=fn, out=str(tmp_path))
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        env[distributed.ENV_COORD] = f"127.0.0.1:{port}"
        env[distributed.ENV_NPROC] = "2"
        env[distributed.ENV_PID] = str(rank)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", script], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    outs = [p.communicate(timeout=240) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, f"rank failed:\n{out}\n{err[-2000:]}"

    s0 = np.load(tmp_path / "ts_snr_rank0.npy")
    s1 = np.load(tmp_path / "ts_snr_rank1.npy")
    np.testing.assert_array_equal(s0, s1)  # identical result everywhere
    np.testing.assert_array_equal(np.load(tmp_path / "ts_peak_rank0.npy"),
                                  np.load(tmp_path / "ts_peak_rank1.npy"))
    # and it equals the sequential single-process sweep
    from pypulsar_tpu.io import filterbank
    from pypulsar_tpu.parallel.staged import sweep_flat

    dms = np.linspace(0.0, 100.0, 12)
    whole = sweep_flat(filterbank.FilterbankFile(fn), dms, nsub=8,
                       group_size=4, chunk_payload=2048).steps[0].result
    # ranks ran single-device CPU; this process compiles under the 8-way
    # virtual mesh conftest — different XLA reduction layouts move the
    # f32 chunk moments by ulps, so the cross-config check uses the
    # engine's documented f32 tolerance (ranks themselves match exactly)
    np.testing.assert_allclose(s0, whole.snr, rtol=1e-5, atol=1e-4)


def test_time_shard_events_match_flat(tmp_path):
    """--all-events composes with time sharding: window-local per-chunk
    peak records concatenate in rank order to exactly the sequential
    sweep's chunk sequence, so the multi-event list is identical."""
    from pypulsar_tpu.io import filterbank
    from pypulsar_tpu.parallel.staged import sweep_flat
    from pypulsar_tpu.parallel.sweep import finalize_sweep, merge_accum_parts

    fn = str(tmp_path / "tse.fil")
    # one pulse per window: t0=2000 lands in rank 0's half, and a second
    # injection at t=6.1 s in rank 1's half proves cross-window events
    from pypulsar_tpu.io.filterbank import FilterbankFile
    from pypulsar_tpu.io import filterbank as _fb_mod

    _write_fil(fn, dm=60.0, t0=2000, seed=7, T=8192)
    fb0 = FilterbankFile(fn)
    data = fb0.get_samples(0, 8192)
    freqs = 1500.0 - 2.0 * np.arange(32)
    bins = numpy_ref.bin_delays(60.0, freqs, 1e-3)
    for c in range(32):
        idx = 6100 + bins[c]
        if idx < 8192:
            data[idx, c] += 10.0
    hdr = dict(nchans=32, tsamp=1e-3, fch1=1500.0, foff=-2.0,
               tstart=55000.0, nbits=32, nifs=1, source_name="DTEST")
    _fb_mod.write_filterbank(fn, hdr, data)

    dms = np.linspace(0.0, 100.0, 12)
    whole_res = sweep_flat(FilterbankFile(fn), dms, nsub=8, group_size=4,
                           chunk_payload=2048,
                           keep_chunk_peaks=True).steps[0].result
    plan = None
    parts = []
    for rank in (0, 1):
        plan, acc = distributed.time_shard_local_accum(
            fn, dms, rank, 2, nsub=8, group_size=4, chunk_payload=2048,
            keep_chunk_peaks=True)
        parts.append(acc)
    assert len(parts[0].chunk_mb) + len(parts[1].chunk_mb) == 4
    merged = merge_accum_parts(parts)
    res = finalize_sweep(plan, merged.n, merged.s, merged.ss, merged.mb,
                         merged.ab, merged.baseline_sum,
                         chunk_mb=list(merged.chunk_mb),
                         chunk_ab=list(merged.chunk_ab))
    ev_whole = whole_res.events(6.0)
    ev_shard = res.events(6.0)
    assert len(ev_whole) == len(ev_shard) and ev_whole
    for a, b in zip(ev_whole, ev_shard):
        assert a == b
    # events from BOTH windows made it through the merge
    samples = [e["sample"] for e in ev_shard]
    assert min(samples) < 4096 <= max(samples)


def test_cli_time_shard_single_process(tmp_path, monkeypatch, capsys):
    """`sweep --time-shard` with no coordinator degenerates to the plain
    flat sweep and writes the same .cands."""
    from pypulsar_tpu.cli.sweep import main

    monkeypatch.chdir(tmp_path)
    _write_fil(str(tmp_path / "one.fil"), dm=60.0, t0=6000, seed=3, T=8192)
    rc = main(["one.fil", "--numdms", "12", "--dmstep", "9.0", "-s", "8",
               "--threshold", "7", "--chunk", "2048"])
    assert rc == 0
    plain = (tmp_path / "one.cands").read_text()
    os.remove(tmp_path / "one.cands")
    rc = main(["one.fil", "--numdms", "12", "--dmstep", "9.0", "-s", "8",
               "--threshold", "7", "--chunk", "2048", "--time-shard"])
    assert rc == 0
    assert (tmp_path / "one.cands").read_text() == plain

    # --all-events parity through the CLI (chunk peaks ride AccumParts)
    rc = main(["one.fil", "--numdms", "12", "--dmstep", "9.0", "-s", "8",
               "--threshold", "7", "--chunk", "2048", "--all-events",
               "-o", "ev_plain"])
    assert rc == 0
    rc = main(["one.fil", "--numdms", "12", "--dmstep", "9.0", "-s", "8",
               "--threshold", "7", "--chunk", "2048", "--all-events",
               "--time-shard", "-o", "ev_shard"])
    assert rc == 0
    assert ((tmp_path / "ev_shard.events").read_text()
            == (tmp_path / "ev_plain.events").read_text())
    assert ((tmp_path / "ev_shard.pulses").read_text()
            == (tmp_path / "ev_plain.pulses").read_text())


_TS_CLI_RANK_SCRIPT = textwrap.dedent("""
    import os, sys
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, {repo!r})
    os.chdir({out!r})
    rank = os.environ["PYPULSAR_TPU_PROCESS_ID"]
    from pypulsar_tpu.cli.sweep import main
    rc = main([{fn!r}, "--time-shard", "--numdms", "12", "--dmstep", "9.0",
               "-s", "8", "--threshold", "7", "--chunk", "2048",
               "--all-events"])
    assert rc == 0
    print("RANK", rank, "OK")
""")


def test_cli_time_shard_two_process(tmp_path):
    """`sweep --time-shard` under 2 real jax.distributed CPU ranks: each
    rank streams half the file, rank 0 writes the .cands, and it matches
    a plain single-process sweep of the whole file."""
    _require_cpu_collectives()
    from pypulsar_tpu.cli.sweep import main

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    fn = str(tmp_path / "one.fil")
    _write_fil(fn, dm=60.0, t0=6000, seed=3, T=8192)

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    script = _TS_CLI_RANK_SCRIPT.format(repo=repo, fn=fn, out=str(tmp_path))
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        env[distributed.ENV_COORD] = f"127.0.0.1:{port}"
        env[distributed.ENV_NPROC] = "2"
        env[distributed.ENV_PID] = str(rank)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", script], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    outs = [p.communicate(timeout=240) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, f"rank failed:\n{out}\n{err[-2000:]}"
    sharded = (tmp_path / "one.cands").read_text()
    rows = [ln.split() for ln in sharded.splitlines()
            if ln.strip() and not ln.startswith("#")]
    assert rows, "no candidates written"
    # the injected DM=60 pulsar is the strongest candidate
    best = max(rows, key=lambda r: float(r[1]))
    assert abs(float(best[0]) - 60.0) <= 10.0
    assert float(best[1]) > 8.0
    # --all-events rode the cross-rank peak gather: event rows from BOTH
    # halves of the file made it into rank 0's artifact, and the plain
    # single-process run reproduces them byte-for-byte
    events = (tmp_path / "one.events").read_text()
    ev_rows = [ln.split() for ln in events.splitlines()
               if ln.strip() and not ln.startswith("#")]
    assert ev_rows  # the injected pulse (t=6.0 s, rank 1's window)
    assert any(abs(float(r[2]) - 6.0) < 0.1 for r in ev_rows)
    from pypulsar_tpu.cli.sweep import main as sweep_main
    import os as _os
    _cwd = _os.getcwd()
    _os.chdir(tmp_path)
    try:
        assert sweep_main([fn, "--numdms", "12", "--dmstep", "9.0",
                           "-s", "8", "--threshold", "7", "--chunk",
                           "2048", "--all-events", "-o", "seq"]) == 0
    finally:
        _os.chdir(_cwd)
    assert (tmp_path / "seq.events").read_text() == events


_CLI_RANK_SCRIPT = textwrap.dedent("""
    import os, sys
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, {repo!r})
    os.chdir({out!r})
    # rank from the env, NOT jax.process_index(): touching the backend
    # before the CLI's own distributed.initialize() would break init
    rank = os.environ["PYPULSAR_TPU_PROCESS_ID"]
    from pypulsar_tpu.cli.sweep import main
    rc = main([{f0!r}, {f1!r}, "--ddplan", "--hidm", "100", "-s", "8",
               "--group-size", "4", "--threshold", "6",
               "-o", "rank" + rank])
    assert rc == 0
    print("RANK", rank, "OK")
""")


def test_cli_sweep_ddplan_two_process(tmp_path):
    """The user-facing path (VERDICT r3 item 5): two jax.distributed CPU
    ranks run ``cli sweep --ddplan`` over two files; each rank writes the
    .cands artifact for its own file share and both write identical
    merged tables."""
    _require_cpu_collectives()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    f0 = str(tmp_path / "a.fil")
    f1 = str(tmp_path / "b.fil")
    _write_fil(f0, dm=40.0, t0=2000, seed=0)
    _write_fil(f1, dm=90.0, t0=5000, seed=1)

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    script = _CLI_RANK_SCRIPT.format(repo=repo, f0=f0, f1=f1,
                                     out=str(tmp_path))
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        env[distributed.ENV_COORD] = f"127.0.0.1:{port}"
        env[distributed.ENV_NPROC] = "2"
        env[distributed.ENV_PID] = str(rank)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", script], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    outs = [p.communicate(timeout=240) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, f"rank failed:\n{out}\n{err[-2000:]}"

    # per-file artifacts written by the owning rank (round-robin share)
    assert (tmp_path / "a.cands").exists()
    assert (tmp_path / "b.cands").exists()
    # each rank wrote a merged table; contents must be identical
    m0 = (tmp_path / "rank0_merged.cands").read_text()
    m1 = (tmp_path / "rank1_merged.cands").read_text()
    assert m0 == m1 and len(m0.splitlines()) > 2
    # both files' candidates are in the merged table
    assert "a.fil" in m0 and "b.fil" in m0


def test_multi_host_sweep_two_process(tmp_path):
    """Real jax.distributed: 2 CPU ranks, disjoint file shares, merged
    candidate tables identical on both ranks and covering both files."""
    _require_cpu_collectives()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    f0 = str(tmp_path / "a.fil")
    f1 = str(tmp_path / "b.fil")
    _write_fil(f0, dm=40.0, t0=2000, seed=0)
    _write_fil(f1, dm=90.0, t0=5000, seed=1)

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    script = _RANK_SCRIPT.format(repo=repo, f0=f0, f1=f1, out=str(tmp_path))
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)  # no virtual device mesh in the ranks
        env[distributed.ENV_COORD] = f"127.0.0.1:{port}"
        env[distributed.ENV_NPROC] = "2"
        env[distributed.ENV_PID] = str(rank)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", script], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    outs = [p.communicate(timeout=240) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, f"rank failed:\n{out}\n{err[-2000:]}"

    m0 = np.load(tmp_path / "merged_rank0.npy")
    m1 = np.load(tmp_path / "merged_rank1.npy")
    np.testing.assert_array_equal(m0, m1)  # same merged table everywhere
    assert set(m0[:, 0].astype(int)) == {0, 1}  # both hosts' files present


def _write_fil8(path, dm, t0, seed, C=32, T=8192, dt=1e-3):
    """8-bit variant for the host-downsample wire-path tests."""
    from pypulsar_tpu.io import filterbank

    freqs = 1500.0 - 2.0 * np.arange(C)
    rng = np.random.RandomState(seed)
    data = rng.randint(0, 160, size=(T, C)).astype(np.uint8)
    bins = numpy_ref.bin_delays(dm, freqs, dt)
    for c in range(C):
        for k in range(4):
            idx = t0 + k + bins[c]
            if idx < T:
                data[idx, c] += 60
    hdr = dict(nchans=C, tsamp=dt, fch1=1500.0, foff=-2.0, tstart=55000.0,
               nbits=8, nifs=1, source_name="DTEST8")
    filterbank.write_filterbank(path, hdr, np.minimum(data, 255))


def test_host_downsample_matches_device_path(tmp_path, monkeypatch):
    """VERDICT r4 item 3: host-side downsample-before-wire (exact integer
    bin sums shipped as uint16) is bit-identical to the device
    downsample path, while shipping 2/factor B per raw sample."""
    from pypulsar_tpu.io import filterbank
    from pypulsar_tpu.parallel.staged import (_host_downsample_wins,
                                              _ReaderSource, sweep_flat)

    fn = str(tmp_path / "hds.fil")
    _write_fil8(fn, dm=60.0, t0=6000, seed=9)
    dms = np.linspace(0.0, 100.0, 12)
    src = _ReaderSource(filterbank.FilterbankFile(fn))
    assert _host_downsample_wins(src, 4)       # 2/4 < 1 B/sample
    assert not _host_downsample_wins(src, 2)   # 2/2 = 1 B/sample: no win
    monkeypatch.setenv("PYPULSAR_TPU_HOST_DOWNSAMP", "0")
    dev = sweep_flat(filterbank.FilterbankFile(fn), dms, downsamp=4,
                     nsub=8, group_size=4,
                     chunk_payload=1024).steps[0].result
    monkeypatch.setenv("PYPULSAR_TPU_HOST_DOWNSAMP", "1")
    host = sweep_flat(filterbank.FilterbankFile(fn), dms, downsamp=4,
                      nsub=8, group_size=4,
                      chunk_payload=1024).steps[0].result
    np.testing.assert_array_equal(host.snr, dev.snr)
    np.testing.assert_array_equal(host.peak_sample, dev.peak_sample)
    np.testing.assert_array_equal(host.mean, dev.mean)


def test_time_sharded_ddplan_single_count_matches_staged(tmp_path):
    """count=1 time_sharded_ddplan equals the sequential staged sweep."""
    from pypulsar_tpu.io import filterbank
    from pypulsar_tpu.parallel.staged import sweep_ddplan
    from pypulsar_tpu.plan.ddplan import Observation

    fn = str(tmp_path / "tsp.fil")
    _write_fil(fn, dm=60.0, t0=6000, seed=4)
    fil = filterbank.FilterbankFile(fn)
    obs = Observation(dt=1e-3, fctr=1469.0, BW=64.0, numchan=32)
    plan = obs.gen_ddplan(0.0, 120.0)
    seq = sweep_ddplan(fil, plan, nsub=8, group_size=4, chunk_payload=1024)
    ts = distributed.time_sharded_ddplan(
        filterbank.FilterbankFile(fn), plan, nsub=8, group_size=4,
        chunk_payload=1024, rank=0, count=1)
    assert len(ts.steps) == len(seq.steps)
    assert [s.downsamp for s in ts.steps] == [s.downsamp for s in seq.steps]
    for a, b in zip(ts.steps, seq.steps):
        np.testing.assert_allclose(a.result.snr, b.result.snr,
                                   rtol=1e-6, atol=1e-5)
        np.testing.assert_array_equal(a.result.peak_sample,
                                      b.result.peak_sample)
    best = ts.best(1)[0]
    assert abs(best["dm"] - 60.0) <= 6.0 and best["snr"] > 8.0


def test_time_sharded_ddplan_inprocess_merge_matches(tmp_path):
    """Two in-process windows per DDstep merge to the sequential staged
    result (the collective-free half of time_sharded_ddplan)."""
    from pypulsar_tpu.io import filterbank
    from pypulsar_tpu.parallel.staged import sweep_ddplan
    from pypulsar_tpu.parallel.sweep import finalize_sweep, merge_accum_parts
    from pypulsar_tpu.plan.ddplan import Observation

    fn = str(tmp_path / "tsp2.fil")
    _write_fil8(fn, dm=60.0, t0=6000, seed=5)
    fil = filterbank.FilterbankFile(fn)
    obs = Observation(dt=1e-3, fctr=1469.0, BW=64.0, numchan=32)
    plan = obs.gen_ddplan(0.0, 1000.0)
    assert any(s.downsamp > 1 for s in plan.DDsteps)  # staged for real
    seq = sweep_ddplan(fil, plan, nsub=8, group_size=4, chunk_payload=1024)
    for i, st in enumerate(plan.DDsteps):
        parts = []
        sp = None
        for rank in (0, 1):
            sp, acc = distributed.time_shard_local_accum(
                fn, np.asarray(st.DMs), rank, 2, nsub=8, group_size=4,
                chunk_payload=1024, downsamp=int(st.downsamp))
            parts.append(acc)
        merged = merge_accum_parts(parts)
        res = finalize_sweep(sp, merged.n, merged.s, merged.ss, merged.mb,
                             merged.ab, merged.baseline_sum)
        np.testing.assert_array_equal(res.peak_sample,
                                      seq.steps[i].result.peak_sample)
        np.testing.assert_allclose(res.snr, seq.steps[i].result.snr,
                                   rtol=1e-9, atol=1e-9)


_TS_DDPLAN_CLI_RANK_SCRIPT = textwrap.dedent("""
    import os, sys
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, {repo!r})
    os.chdir({out!r})
    rank = os.environ["PYPULSAR_TPU_PROCESS_ID"]
    from pypulsar_tpu.cli.sweep import main
    rc = main([{fn!r}, "--time-shard", "--ddplan", "--hidm", "1000",
               "-s", "8", "--group-size", "4", "--threshold", "7",
               "--chunk", "1024"])
    assert rc == 0
    print("RANK", rank, "OK")
""")


def test_cli_time_shard_ddplan_two_process(tmp_path):
    """`sweep --time-shard --ddplan` (VERDICT r4 item 3) under 2 real
    jax.distributed CPU ranks: every DDstep's time axis splits across
    ranks, rank 0 writes the .cands, and the artifact equals the
    sequential single-process --ddplan run bit-for-bit."""
    _require_cpu_collectives()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    fn = str(tmp_path / "tsdd.fil")
    _write_fil8(fn, dm=60.0, t0=6000, seed=3)

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    script = _TS_DDPLAN_CLI_RANK_SCRIPT.format(repo=repo, fn=fn,
                                               out=str(tmp_path))
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        env[distributed.ENV_COORD] = f"127.0.0.1:{port}"
        env[distributed.ENV_NPROC] = "2"
        env[distributed.ENV_PID] = str(rank)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", script], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    outs = [p.communicate(timeout=240) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, f"rank failed:\n{out}\n{err[-2000:]}"
    sharded = (tmp_path / "tsdd.cands").read_text()
    rows = [ln.split() for ln in sharded.splitlines()
            if ln.strip() and not ln.startswith("#")]
    assert rows, "no candidates written"
    best = max(rows, key=lambda r: float(r[1]))
    assert abs(float(best[0]) - 60.0) <= 17.0
    assert float(best[1]) > 8.0
    # sequential single-process --ddplan reproduces the artifact
    from pypulsar_tpu.cli.sweep import main as sweep_main
    _cwd = os.getcwd()
    os.chdir(tmp_path)
    try:
        assert sweep_main([fn, "--ddplan", "--hidm", "1000", "-s", "8",
                           "--group-size", "4", "--threshold", "7",
                           "--chunk", "1024", "-o", "seqdd"]) == 0
    finally:
        os.chdir(_cwd)
    assert (tmp_path / "seqdd.cands").read_text() == sharded


_TS_DATS_CLI_RANK_SCRIPT = textwrap.dedent("""
    import os, sys
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, {repo!r})
    os.chdir({out!r})
    rank = os.environ["PYPULSAR_TPU_PROCESS_ID"]
    from pypulsar_tpu.cli.sweep import main
    rc = main([{fn!r}, "--time-shard", "--numdms", "3", "--dmstep", "30.0",
               "-s", "8", "--group-size", "4", "--threshold", "7",
               "--chunk", "1024", "--write-dats"])
    assert rc == 0
    print("RANK", rank, "OK")
""")


def test_cli_time_shard_write_dats_two_process(tmp_path):
    """`sweep --time-shard --write-dats` (VERDICT r4 item 3): each rank
    writes its window's .dat segments, rank 0 concatenates — the result
    is bit-identical to the single-process streamed writer, with .inf
    sidecars carrying the full length."""
    _require_cpu_collectives()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    fn = str(tmp_path / "tswd.fil")
    _write_fil8(fn, dm=60.0, t0=6000, seed=7)

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    script = _TS_DATS_CLI_RANK_SCRIPT.format(repo=repo, fn=fn,
                                             out=str(tmp_path))
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        env[distributed.ENV_COORD] = f"127.0.0.1:{port}"
        env[distributed.ENV_NPROC] = "2"
        env[distributed.ENV_PID] = str(rank)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", script], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    outs = [p.communicate(timeout=240) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, f"rank failed:\n{out}\n{err[-2000:]}"
    from pypulsar_tpu.io import filterbank
    from pypulsar_tpu.io.infodata import InfoData
    from pypulsar_tpu.parallel.staged import write_dats_streamed

    dms = [0.0, 30.0, 60.0]
    ref_out = str(tmp_path / "refdats")
    write_dats_streamed(ref_out, filterbank.FilterbankFile(fn), dms,
                        nsub=8, group_size=4, chunk_payload=1024)
    for dm in dms:
        got = np.fromfile(tmp_path / f"tswd_DM{dm:.2f}.dat", np.float32)
        ref = np.fromfile(f"{ref_out}_DM{dm:.2f}.dat", np.float32)
        np.testing.assert_array_equal(got, ref)
        assert not (tmp_path / f"tswd_DM{dm:.2f}.w0.dat").exists()
        inf = InfoData(str(tmp_path / f"tswd_DM{dm:.2f}.inf"))
        assert int(inf.N) == 8192


def test_reroot_source_windowed_and_masked(tmp_path):
    """_reroot_source (seek-resume) preserves a window's end bound and
    the mask wrapper, and the re-rooted stream yields the same blocks
    the original stream yields past the cursor."""
    from pypulsar_tpu.parallel.staged import _ReaderSource, _reroot_source
    from pypulsar_tpu.io import filterbank

    fn = str(tmp_path / "rr.fil")
    _write_fil8(fn, dm=60.0, t0=6000, seed=2)
    src = _ReaderSource(filterbank.FilterbankFile(fn), 0, 6144)
    seeked = _reroot_source(src, 2048)
    assert (seeked.start, seeked.end) == (2048, 6144)
    orig = [(p, np.asarray(b)) for p, b in
            src.chan_major_blocks(2048, 64)]
    re = [(p, np.asarray(b)) for p, b in
          seeked.chan_major_blocks(2048, 64)]
    assert [p for p, _ in re] == [p for p, _ in orig if p >= 2048]
    for (p1, b1), (p2, b2) in zip(re, [o for o in orig if o[0] >= 2048]):
        np.testing.assert_array_equal(b1, b2)
