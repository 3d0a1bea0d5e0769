"""Run benchmark cells the way the driver's check does, and keep what each
run left behind.

The driver does not run a cell from `/root/repo` on a warm cache: it unpacks
the committed files at a path of its own, starts from an empty
`bench/.jax_cache`, and runs the new cell first on the PARENT's program under
this PR's `BENCHMARK.json` and `bench/` (PR 31 was refused there: "the
benchmark exited with code 1", and nothing recorded which path ended it).
This script reproduces those conditions in one chip call:

    python3 ci/driver_check.py --src <tree> --dest <path other than the repo>
        [--program <dir holding another gubernator_tpu/>] [--keep-cache]
        --tag <name> --run <workload>:<seed>:<0|1> [--run ...]

`--src` is the tree to copy (a `git archive $(git write-tree)` unpacked under
`.benchcheck/`, or `.` for the tree as it stands); `--program` replaces the
copy's `gubernator_tpu/` (the parent's, from `git archive <commit>
gubernator_tpu`). The cache is emptied before the first run unless
`--keep-cache`; the runs then follow one another on what they compiled.

For every run it records the exit code, the wall, the last stderr lines,
the peak resident memory of the generator (the `bench/run.py` process) and of
the server (its `bench/launcher.py` child), read from `/proc` every two
seconds (`rss_peak_bytes`), and from the context line seconds to healthy,
`setup_s`, the counters the acceptance criteria name; for a traced run also the trace's size on disk and
the wall of `bench/xplane.py --reduce` run once more over it (the harness
gives that child 300 s). One JSON line a run in
`chiprun_out/driver_check/<tag>.jsonl`, stdout and stderr of each run beside
it, and a table on stdout. Exit code 1 if any run's was not 0 or any result
not `correct`.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(ROOT, "chiprun_out", "driver_check")
LEFT_OUT = shutil.ignore_patterns(
    ".git", "chiprun_out", ".jax_cache", ".benchcheck", ".chipcheck", ".out",
    "__pycache__", "*.so", ".pytest_cache",
)


def tree_bytes(path: str) -> int:
    return sum(
        os.path.getsize(os.path.join(d, f))
        for d, _, files in os.walk(path) for f in files
    )


def summary(ctx: dict, res: dict, trace: int) -> dict:
    """What the acceptance criteria read of one run's context and result lines."""
    cache = ctx["cache_entries"]
    out = dict(
        correct=res["correct"], failed=res["failed"], attempted=res["attempted"],
        metrics={k: v["value"] for k, v in res["metrics"].items()},
        memory_peak_bytes=res["device"]["memory_peak_bytes"],
        healthy_s=ctx["walls_s"]["server_healthy"], fill_s=ctx["walls_s"]["fill"],
        total_s=ctx["walls_s"]["total"], cache=cache,
        window_compiles=cache["at_window_end"] - cache["at_window_start"],
        evicted_live_total=ctx["evicted_live_total"], dispatches=ctx["dispatches"],
        generator_errors=ctx["generator"]["errors"],
        not_zero={k: v for k, v in res["compared"].items() if v[0]},
    )
    if trace:
        out["idle_share"] = ctx["trace"]["idle_share_worst_chip"]
        out["busy_s"], out["window_s"] = res["device"]["busy_s"], res["device"]["window_s"]
    return out


def _hwm(pid: int) -> int:
    """Resident bytes of one process: its high-water mark where `/proc` has
    one (VmHWM), else what it holds now (VmRSS, statm); 0 once it is gone."""
    try:
        with open(f"/proc/{pid}/status") as f:
            fields = dict(
                line.split(":", 1) for line in f if line.startswith(("VmHWM", "VmRSS"))
            )
        for key in ("VmHWM", "VmRSS"):
            if key in fields:
                return int(fields[key].split()[0]) * 1024
        with open(f"/proc/{pid}/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        return 0


def _children(pid: int) -> list:
    try:
        with open(f"/proc/{pid}/task/{pid}/children") as f:
            return [int(p) for p in f.read().split()]
    except OSError:
        return []


def _by_parent(pid: int) -> list:
    """Children of `pid` found by their stat's parent field, for a /proc
    without `children` files."""
    out = []
    for name in os.listdir("/proc"):
        if name.isdigit():
            try:
                with open(f"/proc/{name}/stat") as f:
                    if int(f.read().rsplit(")", 1)[1].split()[1]) == pid:
                        out.append(int(name))
            except (OSError, ValueError, IndexError):
                pass
    return out


def _cmdline(pid: int) -> str:
    try:
        with open(f"/proc/{pid}/cmdline", "rb") as f:
            return f.read().replace(b"\0", b" ").decode(errors="replace")
    except OSError:
        return ""


def run_watched(cmd, cwd, out, err) -> "tuple[int, dict]":
    """The command to its end, with the peak resident memory of the
    generator (the command's own process) and of the server child."""
    import resource

    peak = {"generator": 0, "server": 0}
    proc = subprocess.Popen(cmd, cwd=cwd, stdout=out, stderr=err)
    while True:
        peak["generator"] = max(peak["generator"], _hwm(proc.pid))
        for child in _children(proc.pid) or _by_parent(proc.pid):
            if "launcher.py" in _cmdline(child):
                peak["server"] = max(peak["server"], _hwm(child))
        try:
            rc = proc.wait(timeout=2.0)
        except subprocess.TimeoutExpired:
            continue
        # the kernel's own figure, for a machine whose /proc says less: the
        # largest resident set of any descendant waited for so far (KiB)
        peak["largest_descendant_so_far"] = (
            resource.getrusage(resource.RUSAGE_CHILDREN).ru_maxrss * 1024
        )
        return rc, peak


def one_run(dest: str, tag: str, i: int, workload: str, seed: int, trace: int,
            seconds: float) -> dict:
    stem = os.path.join(OUT, f"{tag}.{i}")
    cmd = [sys.executable, "bench/run.py", "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace)]
    t0 = time.monotonic()
    with open(stem + ".out", "wb") as out, open(stem + ".err", "wb") as err:
        rc, rss = run_watched(cmd, dest, out, err)
    rec = {"tag": tag, "workload": workload, "seed": seed, "trace": trace, "rc": rc,
           "wall_s": round(time.monotonic() - t0, 1), "rss_peak_bytes": rss}
    with open(stem + ".err", errors="replace") as f:
        rec["stderr_tail"] = f.read().strip().splitlines()[-12:]
    with open(stem + ".out", errors="replace") as f:
        lines = [ln for ln in f.read().splitlines() if ln.startswith("{")]
    if rc == 0 and len(lines) >= 2:
        rec.update(summary(json.loads(lines[-2]), json.loads(lines[-1]), trace))
    tdir = os.path.join(dest, "bench", ".out", "trace")
    if trace and os.path.isdir(tdir):
        rec["trace_bytes"] = tree_bytes(tdir)
        t0 = time.monotonic()
        p = subprocess.run(
            [sys.executable, "bench/xplane.py", tdir, "--reduce", "tpu"], cwd=dest,
            env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        )
        rec["reduce_s"], rec["reduce_rc"] = round(time.monotonic() - t0, 1), p.returncode
    log = os.path.join(dest, "bench", ".out", "server.log")
    if os.path.exists(log):
        shutil.copy(log, stem + ".server.log")
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--src", default=".")
    ap.add_argument("--dest", required=True)
    ap.add_argument("--program")
    ap.add_argument("--keep-cache", action="store_true")
    ap.add_argument("--tag", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--run", action="append", required=True,
                    metavar="WORKLOAD:SEED:TRACE")
    args = ap.parse_args(argv)
    dest = os.path.abspath(args.dest)
    if dest == ROOT or dest.startswith(ROOT + os.sep):
        ap.error("--dest has to lie outside the repo: a cache entry made there would hit")
    os.makedirs(OUT, exist_ok=True)
    if not (args.keep_cache and os.path.isdir(dest)):
        shutil.rmtree(dest, ignore_errors=True)
        shutil.copytree(os.path.join(ROOT, args.src), dest, ignore=LEFT_OUT)
    if args.program:
        shutil.rmtree(os.path.join(dest, "gubernator_tpu"))
        shutil.copytree(os.path.join(ROOT, args.program, "gubernator_tpu"),
                        os.path.join(dest, "gubernator_tpu"), ignore=LEFT_OUT)
    if not args.keep_cache:
        shutil.rmtree(os.path.join(dest, "bench", ".jax_cache"), ignore_errors=True)
    bad = 0
    with open(os.path.join(OUT, args.tag + ".jsonl"), "a") as ledger:
        for i, run in enumerate(args.run):
            workload, seed, trace = run.rsplit(":", 2)
            rec = one_run(dest, args.tag, i, workload, int(seed), int(trace), args.seconds)
            ledger.write(json.dumps(rec) + "\n")
            ledger.flush()
            bad += rec["rc"] != 0 or not rec.get("correct", False)
            short = {k: v for k, v in rec.items() if k not in ("stderr_tail", "workload")}
            print(json.dumps(short), flush=True)
            if rec["rc"] != 0:
                print("\n".join(rec["stderr_tail"]), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
