"""Tier-1's guard of the benchmark's own harness (PERF.md Open question
22 a): `pytest tests/` did not collect `benchmark/tests`, so a change to
the program could break the child's slice budget, the split replay or
the benchmark's data files and tier-1 would not see it.  The cases below
ARE those files' cases, loaded from where they lie and collected here
under their file's name, fixtures included; nothing is copied."""

import importlib.util
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1] / "benchmark"
GUARDED = ("test_child", "test_split_replay", "test_data",
           "test_hevd_mesh4")

# the files import `harness` and `run` as benchmark/tests/conftest.py
# lets them
sys.path.append(str(BENCH))


def _collect(stem: str) -> None:
    spec = importlib.util.spec_from_file_location(
        f"benchmark_tests_{stem}", BENCH / "tests" / f"{stem}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    for name, value in vars(module).items():
        if name.startswith("test_"):
            globals()[f"test_{stem[5:]}__{name[5:]}"] = value
        elif hasattr(value, "_fixture_function") or type(
                value).__name__ == "FixtureFunctionDefinition":
            globals()[name] = value      # a fixture goes by its own name


for _stem in GUARDED:
    _collect(_stem)
# benchmark/ stays at the path's END (run.py puts it first as it is
# imported): `tests.<module>` has to stay this directory's for the other
# files of the worker (tests/test_tlb.py imports tests.test_tlv_default,
# and benchmark/tests has a file of that name too)
sys.path[:] = [p for p in sys.path if p != str(BENCH)] + [str(BENCH)]
