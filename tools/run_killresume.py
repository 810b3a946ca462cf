"""Hour-scale kill-and-resume proof (VERDICT r4 item 4), as one script.

Runs the checkpointed CLI sweep of the north-star file three ways:

1. uninterrupted reference -> {out}/seq.cands
2. the same command SIGKILLed at ~``--kill-frac`` of the file
3. resumed with --resume (seek-resume: the stream re-roots at the
   checkpoint cursor) -> {out}/kr.cands

and verifies kr.cands == seq.cands byte-for-byte, recording the wall
times (the resume wall measures the replay overhead). Each sweep is a
child process that owns the chip alone, one after another; this parent
never touches JAX.

Usage: python tools/run_killresume.py [--trials 4096] [--kill-frac 0.45]
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--fil", default=os.path.join(REPO, "data",
                                                  "northstar_1hr.fil"))
    ap.add_argument("--trials", type=int, default=4096)
    ap.add_argument("--dm-max", type=float, default=500.0)
    ap.add_argument("--kill-frac", type=float, default=0.45)
    ap.add_argument("--checkpoint-every", type=int, default=None,
                    help="chunks between checkpoint saves (default: the "
                         "CLI's 16; toy rehearsals with fewer total "
                         "chunks need 1-2 or no checkpoint ever lands)")
    ap.add_argument("--workdir", default=os.path.join(REPO, "data",
                                                      "killresume"))
    ap.add_argument("--skip-seq", action="store_true",
                    help="reuse an existing {workdir}/seq.cands")
    ap.add_argument("--out", default=os.path.join(
        REPO, "BENCH_r05_killresume.json"))
    return ap.parse_args(argv)


def sweep_argv(a, outbase, ckpt=None, resume=False):
    dmstep = a.dm_max / max(a.trials - 1, 1)
    argv = [sys.executable, "-m", "pypulsar_tpu.cli.sweep", a.fil,
            "--lodm", "0", "--dmstep", f"{dmstep:.16g}",
            "--numdms", str(a.trials), "-s", "64", "--group-size", "32",
            "--threshold", "10", "-o", outbase]
    if ckpt:
        argv += ["--checkpoint", ckpt]
        if a.checkpoint_every is not None:
            argv += ["--checkpoint-every", str(a.checkpoint_every)]
    if resume:
        argv += ["--resume"]
    return argv


def main(argv=None):
    a = parse_args(argv)
    os.makedirs(a.workdir, exist_ok=True)
    seq_out = os.path.join(a.workdir, "seq")
    kr_out = os.path.join(a.workdir, "kr")
    ckpt = os.path.join(a.workdir, "kr.ckpt")
    rec = {"metric": "killresume_resume_wall_seconds"}

    if not a.skip_seq or not os.path.exists(seq_out + ".cands"):
        t0 = time.time()
        subprocess.run(sweep_argv(a, seq_out), check=True)
        rec["seq_wall_seconds"] = round(time.time() - t0, 1)
        print(f"## uninterrupted: {rec['seq_wall_seconds']}s", flush=True)

    # killed run: poll the checkpoint cursor until past kill-frac
    from pypulsar_tpu.io.filterbank import FilterbankFile

    T = FilterbankFile(a.fil).number_of_samples
    for stale in (ckpt, ckpt + ".tmp.npz"):
        if os.path.exists(stale):
            os.remove(stale)
    t0 = time.time()
    proc = subprocess.Popen(sweep_argv(a, kr_out, ckpt=ckpt))
    cursor = 0
    while proc.poll() is None:
        time.sleep(5)
        if os.path.exists(ckpt):
            try:
                with np.load(ckpt) as z:
                    cursor = int(z["cursor"])
            except Exception:  # noqa: BLE001 - mid-replace read race
                continue
            if cursor >= a.kill_frac * T:
                break
    if proc.poll() is not None:
        raise RuntimeError("sweep finished before the kill point; "
                           "lower --kill-frac")
    proc.send_signal(signal.SIGKILL)
    proc.wait()
    killed_at = time.time() - t0
    rec["killed_at_seconds"] = round(killed_at, 1)
    rec["killed_at_cursor"] = cursor
    rec["killed_at_frac"] = round(cursor / T, 3)
    print(f"## SIGKILLed at {killed_at:.0f}s, cursor {cursor} "
          f"({cursor/T*100:.0f}% of the file)", flush=True)

    t0 = time.time()
    subprocess.run(sweep_argv(a, kr_out, ckpt=ckpt, resume=True),
                   check=True)
    rec["resume_wall_seconds"] = round(time.time() - t0, 1)
    rec["value"] = rec["resume_wall_seconds"]

    seq = open(seq_out + ".cands", "rb").read()
    kr = open(kr_out + ".cands", "rb").read()
    rec["bit_identical"] = seq == kr
    rec["unit"] = (f"resume wall seconds after SIGKILL at "
                   f"{rec['killed_at_frac']*100:.0f}% of the "
                   f"{a.trials}-trial north-star sweep (seek-resume); "
                   f"candidate table bit-identical to the uninterrupted "
                   f"run: {rec['bit_identical']}")
    rec["vs_baseline"] = 0.0
    print(json.dumps(rec))
    with open(a.out, "w") as f:
        f.write(json.dumps(rec) + "\n")
    if not rec["bit_identical"]:
        print("## FAIL: resumed .cands differs from uninterrupted",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
