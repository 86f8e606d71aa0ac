"""Scheduling tier: solve-time estimator, admission control, EDF ordering,
hold/release decisions, the no-starvation property, and the service-level
behaviors that ride on them (rejection accounting, deadline misses,
async-dispatch parity, the refresh-tick foreground yield)."""
import math

import numpy as np
import pytest

from repro.graph import generators
from repro.serve import (AdmissionRejected, DeadlineScheduler, FifoScheduler,
                         GraphRegistry, PageRankService, PPRQuery,
                         QueueEntry, SolveTimeEstimator, TenantSpec)
from _hypothesis_compat import given, settings, st


def entry(qid=0, graph="g", deadline=math.inf, tenant="default",
          priority=1, t0=0.0, c=0.85, tol=1e-4):
    """A QueueEntry around a real PPRQuery (the scheduler never solves)."""
    q = PPRQuery(qid=qid, graph=graph, seeds=(0,), c=c, tol=tol)
    return QueueEntry(q=q, t0=t0, tr=None, deadline=deadline,
                      tenant=tenant, priority=priority)


class TestSolveTimeEstimator:
    def test_fallback_chain_bucket_graph_global_default(self):
        est = SolveTimeEstimator(default_s=7.0)
        assert est.estimate("g", 4) == 7.0            # nothing observed
        est.observe("g", 4, 2.0)
        assert est.estimate("g", 4) == 2.0            # exact (graph, bucket)
        assert est.estimate("g", 8) == 2.0            # graph fallback
        assert est.estimate("other", 1) == 2.0        # global fallback

    def test_ewma_math(self):
        est = SolveTimeEstimator(alpha=0.25)
        est.observe("g", 4, 1.0)
        est.observe("g", 4, 2.0)
        assert est.estimate("g", 4) == pytest.approx(1.0 + 0.25 * (2.0 - 1.0))

    def test_exact_bucket_wins_over_fallbacks(self):
        est = SolveTimeEstimator(alpha=1.0)
        est.observe("g", 4, 0.1)
        est.observe("g", 16, 5.0)     # shifts graph + global EWMAs
        assert est.estimate("g", 4) == 0.1

    def test_reset_forgets_everything(self):
        est = SolveTimeEstimator(default_s=0.0)
        est.observe("g", 4, 3.0)
        est.reset()
        assert est.estimate("g", 4) == 0.0
        assert est.snapshot() == {}

    def test_reset_single_graph(self):
        est = SolveTimeEstimator()
        est.observe("a", 8, 0.1)
        est.observe("b", 8, 0.4)
        est.reset(graph="a")
        # a falls through its cleared keys to the global EWMA; b keeps its
        # exact bucket sample
        assert est.estimate("b", 8) == 0.4
        assert est.estimate("a", 8) == est._global

    def test_reset_all_still_works(self):
        est = SolveTimeEstimator(default_s=3.0)
        est.observe("a", 8, 0.1)
        est.reset()
        assert est.estimate("a", 8) == 3.0

    def test_snapshot_is_a_copy(self):
        est = SolveTimeEstimator()
        est.observe("g", 4, 1.0)
        snap = est.snapshot()
        snap.clear()
        assert est.estimate("g", 4) == 1.0

    def test_alpha_validated(self):
        with pytest.raises(ValueError):
            SolveTimeEstimator(alpha=0.0)
        with pytest.raises(ValueError):
            SolveTimeEstimator(alpha=1.5)


class TestFifoScheduler:
    def test_head_group_packed_in_arrival_order(self):
        s = FifoScheduler(max_batch=8)
        s.admit(entry(0, c=0.85))
        s.admit(entry(1, c=0.5))      # different operating point
        s.admit(entry(2, c=0.85))
        group = s.next_group(now=0.0)
        assert [e.q.qid for e in group] == [0, 2]
        assert s.depth() == 1
        assert [e.q.qid for e in s.next_group(now=0.0)] == [1]

    def test_max_batch_caps_a_group(self):
        s = FifoScheduler(max_batch=2)
        for i in range(5):
            s.admit(entry(i))
        assert [e.q.qid for e in s.next_group(0.0)] == [0, 1]
        assert s.depth() == 3

    def test_never_holds(self):
        s = FifoScheduler(max_batch=8)
        s.admit(entry(0, deadline=math.inf))
        assert s.next_group(now=0.0, force=False) is not None

    def test_admission_bound(self):
        s = FifoScheduler(max_batch=8, max_depth=2)
        s.admit(entry(0))
        s.admit(entry(1))
        with pytest.raises(AdmissionRejected) as exc:
            s.admit(entry(2, tenant="t"))
        assert exc.value.reason == "queue_full"
        assert exc.value.tenant == "t"
        assert exc.value.depth == 2

    def test_drain_clears(self):
        s = FifoScheduler(max_batch=8)
        s.admit(entry(0))
        s.admit(entry(1))
        assert [e.q.qid for e in s.drain()] == [0, 1]
        assert s.depth() == 0
        assert s.next_group(0.0) is None


def dl_sched(max_batch=8, tenants=None, max_depth=None, margin=0.0,
             est=None, **kw):
    return DeadlineScheduler(
        max_batch, est if est is not None else SolveTimeEstimator(),
        tenants=tenants, max_depth=max_depth, slack_margin_s=margin, **kw)


class TestDeadlineAdmission:
    def test_per_tenant_bound_is_independent(self):
        s = dl_sched(tenants={"a": TenantSpec(name="a", max_depth=2)})
        s.admit(entry(0, tenant="a"))
        s.admit(entry(1, tenant="a"))
        with pytest.raises(AdmissionRejected) as exc:
            s.admit(entry(2, tenant="a"))
        assert (exc.value.reason, exc.value.tenant) == ("queue_full", "a")
        s.admit(entry(3, tenant="b"))     # other tenants unaffected
        assert s.depth_for("a") == 2 and s.depth_for("b") == 1

    def test_scheduler_wide_bound_is_the_fallback(self):
        s = dl_sched(max_depth=1)
        s.admit(entry(0, tenant="x"))
        with pytest.raises(AdmissionRejected):
            s.admit(entry(1, tenant="x"))
        # the bound is per tenant, not global
        s.admit(entry(2, tenant="y"))

    def test_depth_released_on_dispatch(self):
        s = dl_sched(max_depth=1)
        s.admit(entry(0, tenant="x", deadline=0.0))
        assert s.next_group(now=1.0) is not None
        assert s.depth_for("x") == 0
        s.admit(entry(1, tenant="x"))     # slot freed


class TestDeadlineRelease:
    def test_holds_while_slack_above_margin(self):
        s = dl_sched()
        s.admit(entry(0, deadline=10.0))
        assert s.next_group(now=0.0) is None          # slack 10 > 0: hold
        assert [e.q.qid for e in s.next_group(now=10.0)] == [0]

    def test_margin_releases_early(self):
        s = dl_sched(margin=3.0)
        s.admit(entry(0, deadline=10.0))
        assert s.next_group(now=6.0) is None          # slack 4 > margin 3
        assert s.next_group(now=7.0) is not None      # slack 3 <= margin

    def test_estimate_shifts_the_release_point(self):
        est = SolveTimeEstimator()
        est.observe("g", 1, 2.0)
        s = dl_sched(est=est)
        s.admit(entry(0, deadline=10.0))
        assert s.next_group(now=7.0) is None          # 10 - 7 - 2 = 1 > 0
        assert s.next_group(now=8.0) is not None      # slack 0

    def test_full_bucket_releases_regardless_of_slack(self):
        s = dl_sched(max_batch=2)
        s.admit(entry(0, deadline=math.inf))
        assert s.next_group(now=0.0) is None
        s.admit(entry(1, deadline=math.inf))
        assert len(s.next_group(now=0.0)) == 2

    def test_force_releases_unbounded_deadlines(self):
        """Regression: all-infinite-slack groups (no deadline anywhere)
        must still elect a candidate for the force path."""
        s = dl_sched()
        s.admit(entry(0, deadline=math.inf))
        assert s.next_group(now=0.0, force=False) is None
        assert [e.q.qid for e in s.next_group(now=0.0, force=True)] == [0]

    def test_edf_across_groups(self):
        s = dl_sched()
        s.admit(entry(0, graph="slow", deadline=20.0))
        s.admit(entry(1, graph="fast", deadline=5.0))
        group = s.next_group(now=30.0)                # both overdue
        assert [e.q.qid for e in group] == [1]        # earliest deadline

    def test_within_group_order_deadline_then_priority(self):
        s = dl_sched()
        s.admit(entry(0, deadline=9.0, priority=1))
        s.admit(entry(1, deadline=5.0, priority=1))
        s.admit(entry(2, deadline=5.0, priority=3))
        group = s.next_group(now=10.0)
        assert [e.q.qid for e in group] == [2, 1, 0]  # ties -> priority

    def test_tenants_share_a_device_batch(self):
        s = dl_sched()
        s.admit(entry(0, tenant="a", deadline=5.0))
        s.admit(entry(1, tenant="b", deadline=6.0))
        assert len(s.next_group(now=10.0)) == 2       # merged per group key

    def test_min_slack(self):
        est = SolveTimeEstimator()
        est.observe("g", 1, 1.0)
        s = dl_sched(est=est)
        assert s.min_slack(now=0.0) == math.inf
        s.admit(entry(0, deadline=10.0))
        assert s.min_slack(now=4.0) == pytest.approx(5.0)

    def test_drain_most_urgent_first_and_clears(self):
        s = dl_sched()
        s.admit(entry(0, graph="a", deadline=9.0))
        s.admit(entry(1, graph="b", deadline=3.0))
        assert [e.q.qid for e in s.drain()] == [1, 0]
        assert s.depth() == 0 and s.depth_for("default") == 0


class TestNoStarvationProperty:
    @settings(max_examples=50, deadline=None)
    @given(st.lists(
        st.tuples(st.floats(0.0, 8.0, allow_nan=False),   # arrival time
                  st.floats(0.1, 5.0, allow_nan=False),   # latency budget
                  st.integers(0, 2)),                     # graph index
        min_size=1, max_size=30))
    def test_no_admitted_query_starved_past_deadline_plus_one_tick(
            self, arrivals):
        """Drive a synthetic clock in fixed ticks, draining every
        release-ready group per tick: with a cold estimator and zero
        margin, every admitted entry must dispatch by the first tick at or
        after its deadline — i.e. no later than deadline + one tick."""
        dt = 0.5
        s = dl_sched(max_batch=4)
        pending = sorted(((t, t + budget, f"g{gi}") for t, budget, gi
                          in arrivals), key=lambda e: e[0])
        deadlines, dispatched = {}, {}
        horizon = max(d for _, d, _ in pending) + 2 * dt
        qid, now = 0, 0.0
        while now <= horizon:
            while pending and pending[0][0] <= now:
                t, d, graph = pending.pop(0)
                s.admit(entry(qid, graph=graph, deadline=d, t0=t))
                deadlines[qid] = d
                qid += 1
            while True:                     # drain all release-ready groups
                group = s.next_group(now=now)
                if group is None:
                    break
                for e in group:
                    dispatched[e.q.qid] = now
            now += dt
        assert set(dispatched) == set(deadlines)
        for q, d in deadlines.items():
            assert dispatched[q] <= d + dt, \
                f"query {q} starved: deadline {d}, dispatched {dispatched[q]}"


# ---- service-level integration -------------------------------------------

def make_service(g, **kw):
    reg = GraphRegistry(update_mode=kw.pop("update_mode", "incremental"))
    reg.register("g", g)
    defaults = dict(max_batch=8, cache_capacity=64, max_top_k=8)
    defaults.update(kw)
    return PageRankService(reg, **defaults)


class TestServiceScheduling:
    def test_rejection_stays_outside_the_disposition_invariant(self):
        g = generators.tri_mesh(9, 11)
        svc = make_service(g, admission_depth=1)
        svc.submit(PPRQuery(qid=0, graph="g", seeds=(1,)))
        with pytest.raises(AdmissionRejected):
            svc.submit(PPRQuery(qid=1, graph="g", seeds=(2,)))
        st_ = svc.stats
        assert st_["queries"] == 1            # the reject was never accepted
        assert st_["rejected_queries"] == 1
        svc.run_until_drained()
        st_ = svc.stats
        assert st_["queries"] == (st_["cache_hits"] + st_["solved_queries"]
                                  + st_["dropped_queries"])

    def test_deadline_miss_counted_but_still_answered(self):
        g = generators.tri_mesh(9, 11)
        svc = make_service(g, scheduler="deadline")
        svc.submit(PPRQuery(qid=0, graph="g", seeds=(1,), deadline_s=1e-9))
        results = svc.run_until_drained()
        assert 0 in results                   # missed, not dropped
        assert svc.stats["deadline_misses"] == 1
        assert svc.stats["solved_queries"] == 1

    def test_generous_deadline_never_misses(self):
        g = generators.tri_mesh(9, 11)
        svc = make_service(g, scheduler="deadline", default_deadline_s=60.0)
        svc.submit(PPRQuery(qid=0, graph="g", seeds=(1,)))
        svc.run_until_drained()
        assert svc.stats["deadline_misses"] == 0

    @pytest.mark.parametrize("scheduler", ["fifo", "deadline"])
    def test_async_dispatch_matches_sync_results(self, scheduler):
        g = generators.tri_mesh(13, 17)
        rng = np.random.default_rng(3)
        queries = [(tuple(int(s) for s in rng.choice(g.n, 2, replace=False)))
                   for _ in range(6)]

        def answers(async_dispatch):
            svc = make_service(g, max_batch=4, cache_capacity=0,
                               scheduler=scheduler,
                               async_dispatch=async_dispatch,
                               default_deadline_s=60.0)
            for i, seeds in enumerate(queries):
                svc.submit(PPRQuery(qid=i, graph="g", seeds=seeds, top_k=5))
            return svc.run_until_drained()

        sync, awaited = answers(False), answers(True)
        assert set(sync) == set(awaited)
        for qid in sync:
            np.testing.assert_allclose(awaited[qid].scores, sync[qid].scores,
                                       rtol=1e-5, atol=1e-6)

    def test_held_ticks_counted(self):
        g = generators.tri_mesh(9, 11)
        svc = make_service(g, scheduler="deadline", default_deadline_s=60.0)
        svc.submit(PPRQuery(qid=0, graph="g", seeds=(1,)))
        assert not svc.tick()                 # plenty of slack: held
        assert svc.pending() == 1             # still queued, not dropped
        assert svc.stats["held_ticks"] == 1
        svc.run_until_drained()               # force path still drains it
        assert svc.stats["solved_queries"] == 1

    def test_refresh_tick_yields_to_foreground_load(self):
        """Regression: the background refresh must defer while foreground
        queries are pending, and resume once the service is idle."""
        g = generators.tri_mesh(13, 17)
        svc = make_service(g, invalidation_radius=1, refresh_batch=4,
                           refresh_rounds=8)
        svc.submit(PPRQuery(qid=0, graph="g", seeds=(2,)))
        svc.run_until_drained()
        svc.update_graph("g", insert=[(0, 120)])
        assert len(svc._refresh) == 1         # near-boundary survivor queued
        svc.submit(PPRQuery(qid=1, graph="g", seeds=(40,)))   # foreground
        assert svc.refresh_tick() == 0        # yields: query is pending
        assert svc.stats["refresh_deferred"] == 1
        assert len(svc._refresh) == 1         # key stays put, not dropped
        while svc.pending():
            svc.tick(force=True)
        assert svc.refresh_tick() == 1        # idle again: refresh resumes
        assert svc.stats["refreshes"] == 1
