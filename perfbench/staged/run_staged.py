"""Run one cell of the benchmark with the staged entries laid over it.

    python3 perfbench/staged/run_staged.py --workload NAME --seed N --seconds S --trace 0|1

``resident.json`` beside this file holds entries that are ready for
``BENCHMARK.json`` and not yet in it (PR 54: tier-1 tests outside
``perfbench/`` pin that file's counts and the count of
``perfbench/readers/``, and a benchmark PR may not edit them; PERF.md
section 7 has the hand-over). :func:`compose` lays them over the
checkout's ``BENCHMARK.json``: each staged cell is appended, then
appended to every list of an end-to-end or per-layer metric that names
its twin, and each staged metric is appended, its reader found under
``staged/readers``. What ``BENCHMARK.json`` already has under the same
name is left as it is, so the day the entries move this does nothing.
Everything else is ``perfbench/run.py``: the same harness, check and
result line, any cell of either file by name."""

import copy
import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(PERFBENCH)
STAGED = os.path.join(HERE, "resident.json")


def compose(benchmark: dict, staged: dict) -> dict:
    """``benchmark`` with ``staged`` laid over it; paths made absolute,
    so the composed file can stand anywhere."""
    out = copy.deepcopy(benchmark)
    out["paths"] = [os.path.join(CHECKOUT, p) for p in benchmark["paths"]]
    if HERE not in out["paths"]:
        out["paths"].append(HERE)
    for config in out["configs"]:
        config["file"] = os.path.join(CHECKOUT, config["file"])
    have = {w["name"] for w in out["workloads"]}
    for cell in staged["workloads"]:
        if cell["name"] in have:
            continue
        out["workloads"].append(dict(cell))
        twin = staged["twins"][cell["name"]]
        for metric in out["end_to_end"] + out["per_layer"]:
            listed = metric.get("workloads")
            if listed and twin in listed and cell["name"] not in listed:
                listed.append(cell["name"])
    have = {m["name"] for m in out["per_layer"]}
    out["per_layer"] += [copy.deepcopy(m) for m in staged["per_layer"]
                         if m["name"] not in have]
    return out


def composed() -> dict:
    with open(os.path.join(CHECKOUT, "BENCHMARK.json"),
              encoding="utf-8") as f:
        benchmark = json.load(f)
    with open(STAGED, encoding="utf-8") as f:
        return compose(benchmark, json.load(f))


def main(argv=None) -> int:
    sys.path.insert(0, PERFBENCH)
    import run as run_module
    fd, path = tempfile.mkstemp(prefix="perfbench-staged-", suffix=".json")
    try:
        with os.fdopen(fd, "w", encoding="utf-8") as f:
            json.dump(composed(), f)
        return run_module.main(argv, benchmark_path=path)
    finally:
        os.unlink(path)


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)   # as perfbench/run.py leaves, and for its reason
