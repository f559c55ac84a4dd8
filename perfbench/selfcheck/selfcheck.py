"""Self-check of the benchmark's harness, at a tiny size on the CPU.

    JAX_PLATFORMS=cpu python3 perfbench/selfcheck/selfcheck.py

It proves nothing about the device. It checks that
1. ``run.py`` without a TPU exits non-zero and prints no result line;
2. the trace reduction reproduces the known busy seconds, idle share,
   kernel seconds and roofline shares of the small recorded trace;
3. the reference's vectorised gear scan equals the byte-by-byte
   recurrence, and its cut rule a plain loop;
4. the window's counting rule, on made-up build records;
5. the harness end to end (chip look skipped) reports ``correct`` true,
   and the same seed twice gives the same per-build storage growth and
   the same ``stored_per_user_byte`` over the same builds;
6. with the timed path broken underneath (``--fault``: the control
   ``cut_mask``, and ``digest_bit``, ``stale_tree``) ``correct`` comes
   out false; so it does where a kept build has lost its manifest
   (``lost_manifest``: ``missing_outputs``) or a stored chunk a byte
   (``stored_chunk_byte``: ``stored_chunks_differing``);
7. where the storages the lane removed stand again with one file in
   them (``remade_storage``) ``correct`` stays true and none of them is
   among the builds checked;
8. a mix with ``"root": "lane"`` meets the session the build before
   left: end to end and traced, ``correct`` true in both resident cells
   and in their twins; every counted build of a resident cell is a
   session hit, the edit's one path is its dirty set and layer a comes
   from the memo; in the twins, a root a build, all three stay 0 and
   every build invalidates the session it found.
"""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(PERFBENCH)
sys.path.insert(0, PERFBENCH)
sys.path.insert(1, CHECKOUT)
TINY = os.path.join(HERE, "tiny", "BENCHMARK.json")

# Read off small.xplane.pb by hand when it was recorded (PR 23, TPU v5
# lite): window 7.5086 s from the opening mark.
KNOWN = {"busy_s": 0.020467554, "sha_s": 0.01691258, "gear_s": 0.000444713,
         "sha_roofline": 1.3348724, "gear_roofline": 11.8833162}


def inner(argv: list[str]) -> int:
    """One run of the harness on the tiny benchmark, chip look skipped;
    the per-build record goes to stderr for the caller."""
    import run as run_module
    from pbharness import driver
    records = []
    original = driver.run_cell

    def recording(run, progress):
        original(run, progress)
        records.extend((b.lane, b.index, b.context_bytes, b.storage_growth)
                       for b in run.counted)
    driver.run_cell = recording
    rc = run_module.main(argv, benchmark_path=TINY, require_tpu=False)
    print("RECORDS " + json.dumps(records), file=sys.stderr)
    return rc


def run_inner(*argv: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--inner", *argv],
        capture_output=True, text=True, env=env, timeout=900)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    result = json.loads(lines[-1]) if lines and lines[-1].startswith("{") \
        else None
    records = [json.loads(ln[8:]) for ln in proc.stderr.splitlines()
               if ln.startswith("RECORDS ")]
    return proc.returncode, result, records[-1] if records else [], proc


def check(name: str, ok: bool, detail: str = "") -> bool:
    print(f"{'ok  ' if ok else 'FAIL'} {name}{': ' + detail if detail else ''}",
          flush=True)
    return ok


def main() -> int:
    import numpy as np
    from pbharness import kernels, stats, xplane
    from pbharness.check import LIMITS
    from reference import cdc
    good = True

    # 1. no TPU, no result
    proc = subprocess.run(
        [sys.executable, os.path.join(PERFBENCH, "run.py"), "--workload",
         "monorepo-cold", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=CHECKOUT, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    good &= check("run.py without a TPU exits non-zero with no result line",
                  proc.returncode != 0 and '"correct"' not in proc.stdout,
                  f"rc={proc.returncode}")

    # 2. the trace reduction on the recorded trace
    trace = xplane.reduce(os.path.join(HERE, "trace", "small.xplane.pb"),
                          "perfbench_window_open", 7.5086)
    peaks = kernels.peaks_for("TPU v5 lite")
    sha_s = sum(s for _, _, s in xplane.kernel_calls(trace,
                                                     "sha256_lanes_pallas"))
    gear_s = sum(s for _, _, s in xplane.kernel_calls(trace,
                                                      "gear_bitmap_flat"))
    got = {"busy_s": trace.busy_s, "sha_s": sha_s, "gear_s": gear_s,
           "sha_roofline": kernels.hbm_roofline_pct(
               trace, "sha256_lanes_pallas", peaks),
           "gear_roofline": kernels.hbm_roofline_pct(
               trace, "gear_bitmap_flat", peaks)}
    for key, want in KNOWN.items():
        good &= check(f"recorded trace: {key}",
                      abs(got[key] - want) <= 1e-6 * want,
                      f"{got[key]!r} against {want!r}")
    idle = 100.0 * (1 - trace.busy_s / trace.window_s)
    good &= check("recorded trace: idle share", abs(idle - 99.72741) < 1e-3,
                  f"{idle:.5f} %")
    good &= check("recorded trace: gaps tile the idle time",
                  abs(sum(e - s for s, e in trace.gaps)
                      - (trace.window_s - trace.busy_s)) < 1e-6)

    # 3. the reference against the plainest forms
    rng = np.random.default_rng(7)
    data = rng.bytes(70000) + b"abc\n" * 40000 + rng.bytes(50001)
    table = cdc.gear_table()
    h, slow = 0, []
    for i, byte in enumerate(data):
        h = ((h << 1) + int(table[byte])) & 0xFFFFFFFF
        if h & ((1 << cdc.AVG_BITS) - 1) == 0:
            slow.append(i)
    good &= check("reference: segmented scan equals the recurrence",
                  cdc.candidates(data).tolist() == slow, f"{len(slow)} hits")
    cuts, prev, cand = [], 0, set(slow)
    for i in range(len(data)):
        n = i + 1 - prev
        if (i in cand and n >= cdc.MIN_SIZE) or n == cdc.MAX_SIZE \
                or i == len(data) - 1:
            cuts.append(i + 1)
            prev = i + 1
    good &= check("reference: cut rule equals the plain loop",
                  cdc.cut_points(data) == cuts, f"{len(cuts)} chunks")
    good &= check("percentiles", stats.percentile([1, 2, 3, 4], 50) == 2.5
                  and stats.percentile(range(101), 90) == 90.0)

    # 4. the counting rule of the many-lane cells
    from pbharness import driver

    def made_up(kind, t_submit, t_done):
        return driver.Build(lane=0, index=0, kind=kind, tag="", context="",
                            storage="", context_bytes=1, t_submit=t_submit,
                            t_done=t_done)
    builds = [made_up("cold", 0, 9), made_up("rebuild", 2, 11),
              made_up("rebuild", 9, 10), made_up("rebuild", 12, 20),
              made_up("rebuild", 19, 21), made_up("cold", 11, 12)]
    driver.count_completed(builds, 10, 20)
    good &= check("a rebuild counts if it completed inside the window",
                  [b.counted for b in builds]
                  == [False, True, True, True, False, False])

    # 5. end to end, twice the same seed
    runs = [run_inner("--workload", "monorepo-edit", "--seed", "41",
                      "--seconds", "5", "--trace", "0") for _ in range(2)]
    for rc, result, _, proc in runs:
        good &= check("tiny monorepo-edit: correct", rc == 0 and bool(result)
                      and result["correct"] and result["failed"] == 0,
                      proc.stdout[-300:] if not result else "")
    (_, _, first, _), (_, _, second, _) = runs
    n = min(len(first), len(second))
    good &= check("same seed twice: the same builds, byte for byte",
                  n >= 2 and [r[:3] for r in first[:n]]
                  == [r[:3] for r in second[:n]], f"{n} builds compared")
    ratio = [sum(r[3] for r in rs[:n]) / sum(r[2] for r in rs[:n])
             for rs in (first, second)] if n else [0, 1]
    good &= check("same seed twice: stored_per_user_byte over the same "
                  "builds", abs(ratio[0] - ratio[1]) <= 1e-3 * ratio[0],
                  f"{ratio[0]:.6f} and {ratio[1]:.6f}")
    rc, result, _, proc = run_inner("--workload", "farm-churn", "--seed",
                                    "42", "--seconds", "6", "--trace", "1")
    good &= check("tiny farm-churn traced: correct, no device metric",
                  rc == 0 and bool(result) and result["correct"]
                  and not any("roofline" in k or k == "device_idle_pct"
                              for k in result["metrics"])
                  and "busy_s" not in result["device"],
                  proc.stdout[-300:] if not result else "")

    # 6. broken underneath
    for workload, fault, count in (
            ("monorepo-cold", "cut_mask", "cut_points_differing"),
            ("farm-unchanged", "cut_mask", "cut_points_differing"),
            # the program's own chunk store refuses the chunk, every
            # build fails and none is left to check
            ("monorepo-cold", "digest_bit", None),
            ("monorepo-edit", "stale_tree", "tar_members_differing"),
            ("monorepo-cold", "lost_manifest", "missing_outputs"),
            ("monorepo-edit", "stored_chunk_byte",
             "stored_chunks_differing")):
        rc, result, _, proc = run_inner(
            "--workload", workload, "--seed", "43", "--seconds", "4",
            "--trace", "0", "--fault", fault)
        good &= check(f"{workload} with {fault}: correct comes out false, "
                      f"by {count or 'no build to check'}",
                      bool(result) and result["correct"] is False
                      and (result["check"][count]["value"] >= 1 if count
                           else result["check"]["checked"]["builds"] == 0),
                      proc.stdout[-300:] if not result else " ".join(
                          f"{k} {result['check'][k]['value']}"
                          for k in LIMITS if result["check"][k]["value"]))

    # 7. what stands on disk without the lane's leave is not checked
    rc, result, _, proc = run_inner(
        "--workload", "monorepo-cold", "--seed", "43", "--seconds", "4",
        "--trace", "0", "--fault", "remade_storage")
    remade = [ln.split()[2] for ln in proc.stderr.splitlines()
              if ln.startswith("FAULT remade_storage ")]
    good &= check("monorepo-cold with remade_storage: correct stays true, "
                  "no remade build is checked",
                  bool(result) and result["correct"] is True and bool(remade)
                  and not set(remade) & set(result["check"]["sampled"]),
                  proc.stdout[-300:] if not result
                  else f"remade {remade}, checked "
                       f"{result['check']['sampled']}")
    # 8. one --root a lane: the session the build before left is met
    for workload, resident in (("monorepo-edit-resident", True),
                               ("monorepo-edit", False),
                               ("farm-unchanged-resident", True),
                               ("farm-unchanged", False)):
        rc, result, _, proc = run_inner("--workload", workload, "--seed",
                                        "44", "--seconds", "5", "--trace", "1")
        got = {k: v["value"] for k, v in result["metrics"].items()} \
            if result else {}
        hits = got.get("session_hits_per_build")
        dirty = got.get("session_dirty_paths_per_build")
        memo = got.get("layer_memo_replays_per_build")
        dropped = got.get("session_invalidations_per_build")
        # A farm's window cuts builds in flight at both edges, so a
        # counter's growth per counted build is within a build of 1.
        one_lane = workload.startswith("monorepo")
        slack = 0.0 if one_lane else 0.05

        def every_build(x):
            return x is not None and abs(x - 1.0) <= slack
        if resident and one_lane:
            met = every_build(hits) and dropped == 0.0 \
                and dirty is not None and dirty >= 1.0 and memo == 1.0
        elif resident:
            # Only the lane that edits has a dirty set.
            met = every_build(hits) and dropped == 0.0 \
                and dirty is not None and dirty > 0
        else:
            met = hits == 0.0 and dirty == 0.0 and memo == 0.0 \
                and every_build(dropped)
        good &= check(
            f"tiny {workload} traced: correct, "
            + ("every build meets its session" if resident
               else "no build meets a session"),
            rc == 0 and bool(result) and result["correct"]
            and result["failed"] == 0 and met,
            proc.stdout[-300:] if not result else
            f"hits {hits} dirty {dirty} memo {memo} invalidations {dropped}")
    print("self-check " + ("passed" if good else "FAILED"))
    return 0 if good else 1


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--inner":
        sys.exit(inner(sys.argv[2:]))
    sys.exit(main())
