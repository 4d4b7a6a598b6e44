"""Each control comes out not correct, at a size the CPU holds: the
reference's PageRank in bfloat16, and weights rounded to bfloat16.  On the
chip the same controls run at the cells' own sizes through
``bench/control.py``."""
import pytest

from bench.control import run_control


@pytest.mark.parametrize("cell,control,seconds,failing", [
    ("g500-s22.analytics", "pagerank_bf16", 0.5, "pagerank_max_rel_err"),
    ("g500-s20-serve.ingest", "weights_bf16", 0.5, "written_mismatches"),
    ("g500-s20-serve.ingest", "weights_bf16", 0.5, "untouched_mismatches"),
])
def test_control_is_not_correct(tiny, cell, control, seconds, failing):
    root, bench = tiny
    res = run_control(cell, control, 21, seconds, root=root, bench_dir=bench)
    assert res["correct"] is False
    c = res["checks"][failing]
    assert c["value"] > c["limit"]
