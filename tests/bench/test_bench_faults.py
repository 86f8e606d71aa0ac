"""`correct` catches a broken timed path. Each test plants one fault in the
program underneath a whole run (the chip check skipped, tiny sizes on the
CPU) and sees `correct` come out false:

  state unchanged   the Chebyshev round returns its state as it was;
  half the batch    half of each tick's columns are not solved, their
                    queries answered from the other half;
  answer altered    every served answer's top score is raised where the
                    service materializes it;
  rounds dropped    a global solve runs six rounds fewer than Formula 8
                    asks for its tol.

The fourth fault a cell can have, the exchange between chips left out, has
no place here: every cell runs on one chip.
"""
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import tiny  # noqa: E402

from repro.core import engine as engine_mod  # noqa: E402
from repro.core import pagerank as pagerank_mod  # noqa: E402
from repro.serve import pagerank_service as svc_mod  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("faults"))


@pytest.fixture
def fresh_jit():
    """Planted faults live in traced code: drop compiled programs around
    each test so neither the fault nor a clean program leaks across."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def state_unchanged(monkeypatch):
    monkeypatch.setattr(engine_mod, "_default_cheb_round",
                        lambda y, t, acc, ck: (t, acc))


def half_the_batch(monkeypatch):
    solve = svc_mod._solve_topk_adaptive

    def broken(engine, p, *a, **kw):
        h = p.shape[1] // 2
        return solve(engine, p.at[:, h:2 * h].set(p[:, :h]), *a, **kw)
    monkeypatch.setattr(svc_mod, "_solve_topk_adaptive", broken)


def answer_altered(monkeypatch):
    make = svc_mod.PageRankService._materialize

    def broken(self, q, epoch, idx, scores, cached, batch_size=0):
        scores = np.array(scores, copy=True)
        scores[0] += 1e-3
        return make(self, q, epoch, idx, scores, cached, batch_size)
    monkeypatch.setattr(svc_mod.PageRankService, "_materialize", broken)


def rounds_dropped(monkeypatch):
    make = pagerank_mod.make_schedule
    monkeypatch.setattr(pagerank_mod, "make_schedule",
                        lambda c, tol: make(c, rounds=make(c, tol).rounds - 6))


@pytest.mark.parametrize("workload,fault", [
    ("mesh-1m.global", rounds_dropped),
    ("graph500-19.global", rounds_dropped),
    ("mesh-1m.global", state_unchanged),
    ("graph500-19.global", state_unchanged),
    ("graph500-19.serve", state_unchanged),
    ("graph500-19.serve", answer_altered),
    ("mesh-1m.bulk", state_unchanged),
    ("mesh-1m.bulk", half_the_batch),
    ("mesh-1m.bulk", answer_altered),
])
def test_fault_makes_correct_false(root, fresh_jit, monkeypatch, workload,
                                   fault):
    fault(monkeypatch)
    rc, res = tiny.run_cell(root, workload)
    assert rc == 0 and res is not None
    assert res["correct"] is False
    assert any(c["value"] == "inf" or c["value"] > c["limit"]
               for c in res["checks"].values())
