"""A run's result line: its schema, the checks last, and no result at all
without a TPU."""
import json

import pytest

from bench import run, spec


def test_no_tpu_means_no_result(capsys):
    code = run.main(["--workload", "g500-s22.analytics", "--seed", "1",
                     "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert code != 0 and out.out == ""
    assert "needs a TPU" in out.err


def _last_line(tiny, monkeypatch, capsys, argv):
    root, bench = tiny
    monkeypatch.setattr(spec, "ROOT", root)
    monkeypatch.setattr(spec, "BENCH_DIR", bench)
    assert run.main(argv) == 0
    out = capsys.readouterr()
    return json.loads(out.out.strip().splitlines()[-1]), out.err


@pytest.mark.parametrize("cell,seconds", [("g500-s22.analytics", 0.5),
                                          ("g500-s20-serve.ingest", 0.5)])
def test_result_line_schema(tiny, monkeypatch, capsys, cell, seconds):
    res, err = _last_line(tiny, monkeypatch, capsys,
                          ["--workload", cell, "--seed", str(2**33 + 5),
                           "--seconds", str(seconds), "--trace", "0"])
    root, bench = tiny
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    want = {m["name"] for m in spec.load_cell(cell, root, bench).end_to_end}
    assert set(res["metrics"]) == want
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    tail = err.strip().splitlines()[-len(res["checks"]):]
    for line, (name, c) in zip(tail, res["checks"].items()):
        assert line.startswith(f"check {name}: {c['value']} <= {c['limit']}")


def test_traced_result_line(tiny, monkeypatch, capsys):
    res, _ = _last_line(tiny, monkeypatch, capsys,
                        ["--workload", "g500-s20-serve.ingest", "--seed", "3",
                         "--seconds", "0.5", "--trace", "1"])
    assert res["correct"] is True
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 for v in res["breakdown"].values())
    per_layer = {m["name"] for m in spec.load_cell(
        "g500-s20-serve.ingest").per_layer}
    # host spans are read on any platform; device planes only on a TPU
    assert "flush.maintenance_ms" in res["metrics"]
    assert set(res["metrics"]) <= per_layer
