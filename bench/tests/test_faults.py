"""A run whose timed path is broken underneath comes out not correct, once
for each fault a cell can have: a step that returns its state unchanged,
half of a batch left out, an answer altered where it is produced, and a
flush that loses the edges of a vertex no write touched.  (The exchange
between chips does not exist in these one-chip cells.)"""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import repro.stream.service as service_mod
from repro.core.blockstore import PAD
from repro.serve.scheduler import ServeFrontend

from bench import run
from bench.tests.conftest import add_cell


def _unchanged_store(monkeypatch):
    real = service_mod.batch_update_stats

    def upsert(cbl, *a, **k):
        return cbl, real(cbl, *a, **k)[1]
    monkeypatch.setattr(service_mod, "batch_update_stats", upsert)


def _unchanged_program(monkeypatch):
    real = service_mod.run_program
    monkeypatch.setattr(service_mod, "run_program",
                        lambda cbl, prog, **k: real(cbl, prog,
                                                    **dict(k, max_iters=0)))


def _half_updates(monkeypatch):
    def valid(self, mb):
        m = np.zeros((mb.bucket,), bool)
        m[:mb.lanes // 2] = True
        return m
    monkeypatch.setattr(ServeFrontend, "_valid_mask", valid)


def _altered_write(monkeypatch):
    real = ServeFrontend._fuse

    def fuse(self, mb, field, fill, dtype):
        out = real(self, mb, field, fill, dtype)
        if mb.kind == "update" and dtype == np.float32:
            out[0] += 1.0
        return out
    monkeypatch.setattr(ServeFrontend, "_fuse", fuse)


def _altered_level(monkeypatch):
    real = service_mod.GraphService.analytics

    def analytics(self, name, source=None, **kw):
        out = real(self, name, source=source, **kw)
        return out.at[0].add(1) if name == "bfs" else out
    monkeypatch.setattr(service_mod.GraphService, "analytics", analytics)


def _dropped_vertex(monkeypatch):
    """Each upsert also empties the blocks of the vertex of highest degree
    that no record so far has had as its source."""
    real = service_mod.batch_update_stats
    touched = []

    def upsert(cbl, src, *a, **k):
        new, stats = real(cbl, src, *a, **k)
        nv = new.v_deg.shape[0]
        ids = jnp.where((src >= 0) & (src < nv), src, nv)
        touched.append(jnp.zeros((nv,), bool).at[ids].set(True, mode="drop")
                       | (touched[-1] if touched else False))
        v = jnp.argmax(jnp.where(touched[-1], -1, new.v_deg))
        mine = new.store.owner == v
        store = new.store._replace(
            keys=jnp.where(mine[:, None], PAD, new.store.keys),
            count=jnp.where(mine, 0, new.store.count))
        return new._replace(store=store, v_deg=new.v_deg.at[v].set(0)), stats
    monkeypatch.setattr(service_mod, "batch_update_stats", upsert)


BFS = {"loop": "closed", "repeat": [
    {"kind": "analytics", "program": "bfs", "tenant": "dashboard",
     "class": "batch", "root": "distinct_non_isolated"}]}


@pytest.mark.parametrize("cell,fault", [
    ("g500-s22.analytics", _unchanged_program),
    ("g500-s22.bfs", _altered_level),
    ("g500-s20-serve.ingest", _unchanged_store),
    ("g500-s20-serve.ingest", _half_updates),
    ("g500-s20-serve.ingest", _altered_write),
])
def test_fault_is_not_correct(tiny, monkeypatch, cell, fault):
    root, bench = tiny
    if cell == "g500-s22.bfs":
        add_cell(root, bench, cell, "g500-s22", BFS,
                 {"plan_qps": 1, "plan_lanes_per_request": 1})
    fault(monkeypatch)
    res = run.run_cell(cell, 17, 0.5, False, root=root, bench_dir=bench)
    assert res["correct"] is False, res["checks"]


def test_a_lost_untouched_vertex_is_not_correct(tiny, monkeypatch):
    """Batches small enough that most vertices are never written: the
    sample of untouched edges and the degree of every vertex both see the
    vertex the flush lost."""
    root, bench = tiny
    path = os.path.join(bench, "traffic", "ingest-closed.json")
    mix = json.load(open(path))
    mix["repeat"][0]["records"] = 64
    json.dump(mix, open(path, "w"))
    _dropped_vertex(monkeypatch)
    res = run.run_cell("g500-s20-serve.ingest", 17, 0.5, False, root=root,
                       bench_dir=bench)
    checks = res["checks"]
    assert res["correct"] is False
    assert checks["untouched_mismatches"]["value"] > 0
    assert checks["degree_mismatches"]["value"] > 0
    assert checks["written_mismatches"]["value"] == 0
