"""CollectiveSchedule IR tests: lowering structure, cost-model
monotonicity, fault rewriting, and LO|FA|MO link-fault inference.

Numeric executor equivalence (schedule-executed vs oracle on 1D/2D/3D
tori) runs in a subprocess with 8 forced host devices — see
``fabric_checks.py`` and the slow test at the bottom.
"""
import os
import subprocess
import sys

import pytest

from repro.core import fabric
from repro.core.lofamo import LofamoSim
from repro.core.topology import Torus

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# lowering structure
# ---------------------------------------------------------------------------

def test_all_reduce_lowering_shape():
    s = fabric.lower_all_reduce(Torus((2, 4)), ("a", "b"))
    assert [ (p.kind, p.axis) for p in s.phases ] == [
        ("reduce_scatter", "a"), ("reduce_scatter", "b"),
        ("all_gather", "b"), ("all_gather", "a")]
    # rounds: (2-1) + (4-1) per leg
    assert s.rounds == 2 * (1 + 3)
    # dual-DMA: two concurrent transfers per round
    assert s.n_messages == 2 * s.rounds
    assert s.max_hops == 1


def test_rs_fracs_sum_to_ring_traffic():
    """A bidirectional RS over n ranks injects (n-1)/n of the input."""
    n = 8
    s = fabric.lower_reduce_scatter(Torus((n,)), ("x",))
    assert s.bytes_per_rank(n * 1000) == pytest.approx(
        (n - 1) / n * n * 1000)


def test_all_reduce_fracs_match_2n_minus_1_over_n():
    n = 8
    s = fabric.lower_all_reduce(Torus((n,)), ("x",))
    assert s.bytes_per_rank(1 << 20) == pytest.approx(
        2 * (n - 1) / n * (1 << 20))


def test_dim_ordered_scales_shrink_then_grow():
    s = fabric.lower_all_reduce(Torus((2, 2, 2)), ("x", "y", "z"))
    assert [p.scale for p in s.phases] == [1, 0.5, 0.25, 0.125, 0.25, 0.5]


def test_trivial_axis_has_no_steps():
    s = fabric.lower_all_reduce(Torus((1,)), ("x",))
    assert s.rounds == 0


def test_lowering_validates_axes():
    with pytest.raises(ValueError):
        fabric.lower_all_reduce(Torus((4,)), ("x", "y"))
    with pytest.raises(ValueError):
        fabric.lower("nope", Torus((4,)), ("x",))


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------

def test_cost_monotone_in_bytes():
    s = fabric.lower_all_reduce(Torus((4, 4)), ("x", "y"))
    ts = [fabric.estimate(s, n).total_s for n in (1 << 10, 1 << 15, 1 << 20)]
    assert ts[0] < ts[1] < ts[2]


def test_cost_monotone_in_hops():
    clean = fabric.lower_all_reduce(Torus((8,)), ("x",))
    detoured = fabric.rewrite(
        clean, fabric.FaultMap.normalized(links=[(2, 3)]))
    n = 1 << 20
    assert detoured.max_hops > clean.max_hops
    assert fabric.estimate(detoured, n).total_s \
        > fabric.estimate(clean, n).total_s


def test_cost_monotone_in_ring_size():
    n = 1 << 20
    ts = [fabric.estimate(
        fabric.lower_all_reduce(Torus((k,)), ("x",)), n).total_s
        for k in (2, 4, 8, 16)]
    assert all(a < b for a, b in zip(ts, ts[1:]))


def test_bidirectional_predicted_faster():
    n = 1 << 22
    t = Torus((8,))
    bidi = fabric.estimate(fabric.lower_all_reduce(t, ("x",)), n).total_s
    uni = fabric.estimate(
        fabric.lower_all_reduce(t, ("x",), bidirectional=False), n).total_s
    assert bidi < uni


# ---------------------------------------------------------------------------
# fault rewriting
# ---------------------------------------------------------------------------

def test_rewrite_noop_without_faults():
    s = fabric.lower_all_reduce(Torus((8,)), ("x",))
    assert fabric.rewrite(s, fabric.FaultMap()) is s


def test_dead_node_shrinks_ring_and_drops_from_perms():
    s = fabric.lower_all_reduce(Torus((8,)), ("x",))
    r = fabric.rewrite(s, fabric.FaultMap.normalized(nodes=[3]))
    for ph in r.phases:
        assert ph.ring == (0, 1, 2, 4, 5, 6, 7)
        for st in ph.steps:
            for tr in st.transfers:
                assert all(3 not in pair for pair in tr.perm)
    # the 2->4 transfer cannot route through dead node 3 on a 1D ring:
    # it takes the 6-hop detour the long way around
    assert r.max_hops == 6


def test_dead_link_keeps_ring_bumps_hops():
    s = fabric.lower_all_reduce(Torus((8,)), ("x",))
    r = fabric.rewrite(s, fabric.FaultMap.normalized(links=[(0, 1)]))
    assert all(ph.ring == tuple(range(8)) for ph in r.phases)
    assert r.max_hops == 7  # the long way around the ring


def test_dead_link_2d_detours_through_other_dim():
    s = fabric.lower_all_reduce(Torus((4, 4)), ("x", "y"))
    r = fabric.rewrite(s, fabric.FaultMap.normalized(links=[(0, 4)]),
                       reorder_axes=False)
    # detour 0 -> 4 exists through the orthogonal dimension: 3 hops
    assert 1 < r.max_hops <= 3


def test_axis_reordering_puts_faulted_axis_last():
    s = fabric.lower_all_reduce(Torus((4, 4)), ("x", "y"))
    # kill a link on the x rings (dim 0): x should be reduced last
    r = fabric.rewrite(s, fabric.FaultMap.normalized(links=[(0, 4)]))
    assert r.axes == ("y", "x")
    assert r.axis_dims == (1, 0)
    # numerically the all-reduce is order-invariant; cheaper than not
    # reordering because the detoured axis now moves 1/4 of the bytes
    n = 1 << 22
    r_no = fabric.rewrite(s, fabric.FaultMap.normalized(links=[(0, 4)]),
                          reorder_axes=False)
    assert fabric.estimate(r, n).total_s <= fabric.estimate(r_no, n).total_s


def test_partitioned_fabric_raises():
    # 1D ring of 4: killing both links of rank 1's neighbours cuts it off
    s = fabric.lower_all_reduce(Torus((4,)), ("x",))
    with pytest.raises(fabric.UnroutableError):
        fabric.rewrite(s, fabric.FaultMap.normalized(links=[(0, 1), (1, 2)]))


def test_all_to_all_rejects_dead_nodes_allows_dead_links():
    s = fabric.lower_all_to_all(Torus((4,)), "x")
    with pytest.raises(fabric.UnroutableError):
        fabric.rewrite(s, fabric.FaultMap.normalized(nodes=[2]))
    r = fabric.rewrite(s, fabric.FaultMap.normalized(links=[(1, 2)]))
    assert r.max_hops == 3


def test_mean_and_direction_flags_survive_rewrite():
    s = fabric.lower_all_reduce(Torus((8,)), ("x",), bidirectional=False,
                                mean=True)
    r = fabric.rewrite(s, fabric.FaultMap.normalized(nodes=[0]))
    assert r.mean and not r.bidirectional
    assert all(ph.mean for ph in r.phases if ph.kind == "reduce_scatter")
    assert all(ph.directions == 1 for ph in r.phases if ph.steps)


# ---------------------------------------------------------------------------
# point-to-point lowering (the migration path's unicast)
# ---------------------------------------------------------------------------

def test_p2p_dimension_ordered_route_and_price():
    t = Torus((4, 4, 4))
    dst = t.rank((2, 3, 1))
    s = fabric.lower_p2p(t, 0, dst)
    # dimension-ordered minimal route: hops == torus hop distance, and the
    # route annotation walks X completely before Y before Z
    assert s.max_hops == t.hop_distance(0, dst)
    route = s.phases[0].ring
    assert route[0] == 0 and route[-1] == dst
    changed_dims = []
    for a, b in zip(route, route[1:]):
        ca, cb = t.coords(a), t.coords(b)
        diff = [i for i in range(3) if ca[i] != cb[i]]
        assert len(diff) == 1               # first-neighbour hops only
        changed_dims.append(diff[0])
    assert changed_dims == sorted(changed_dims)   # X fully, then Y, then Z
    # one message end-to-end: estimate equals a single message at hop count
    n = 1 << 20
    assert fabric.estimate(s, n).total_s == pytest.approx(
        fabric.message_time(n, hops=s.max_hops))
    # self-send is free (no transfer)
    assert fabric.estimate(fabric.lower_p2p(t, 3, 3), n).total_s == 0.0


def test_message_time_zero_bytes_prices_header_latency_only():
    """A zero-byte transfer (pure sync step) pays injection + reception +
    per-hop transits, and NOT a phantom 1-byte payload."""
    from repro.core.apelink import NetModel
    net = NetModel()
    for hops in (1, 3, 7):
        assert fabric.message_time(0, net, hops=hops) == pytest.approx(
            net.t_inject + net.t_receive + hops * net.t_hop, rel=1e-12)
    # strictly below any payload-carrying message, monotone at the origin
    assert fabric.message_time(0, net) < fabric.message_time(1, net)
    # fractional sub-byte payloads truncate to the header-only price, not
    # up to a phantom byte
    assert fabric.message_time(0.25, net) == fabric.message_time(0, net)


def test_lower_route_explicit_path():
    t = Torus((4, 4))
    route = (0, 4, 5, 1)                      # a deliberate detour 0 -> 1
    s = fabric.lower_route(t, route)
    assert s.route == route and s.max_hops == 3
    assert fabric.estimate(s, 1 << 20).total_s == pytest.approx(
        fabric.message_time(1 << 20, hops=3))
    with pytest.raises(ValueError):
        fabric.lower_route(t, (0, 5))         # not a first-neighbour link
    with pytest.raises(fabric.UnroutableError):
        fabric.lower_route(t, (0, 1),
                           faults=fabric.FaultMap.normalized(
                               links=[(0, 1)]))


def test_p2p_fault_rewrite_detours_and_costs_more():
    t = Torus((4,))
    s = fabric.lower_p2p(t, 0, 1)
    r = fabric.rewrite(s, fabric.FaultMap.normalized(links=[(0, 1)]))
    assert s.max_hops == 1 and r.max_hops == 3      # 0 -> 3 -> 2 -> 1
    assert r.phases[0].ring == (0, 3, 2, 1)
    n = 1 << 20
    assert fabric.estimate(r, n).total_s > fabric.estimate(s, n).total_s
    # endpoints are recovered from the detoured route annotation: a second
    # rewrite under a DIFFERENT fault map re-lowers src=0, dst=1 (not the
    # detour waypoints) and finds the direct link again
    r2 = fabric.rewrite(r, fabric.FaultMap.normalized(links=[(2, 3)]))
    assert r2.phases[0].ring == (0, 1) and r2.max_hops == 1


def test_p2p_unroutable_and_dead_endpoints():
    with pytest.raises(fabric.UnroutableError):
        fabric.lower_p2p(Torus((2,)), 0, 1,
                         faults=fabric.FaultMap.normalized(links=[(0, 1)]))
    with pytest.raises(fabric.UnroutableError):
        fabric.lower_p2p(Torus((4,)), 0, 1,
                         faults=fabric.FaultMap.normalized(nodes=[1]))
    with pytest.raises(ValueError):
        fabric.lower_p2p(Torus((4,)), 0, 99)
    with pytest.raises(ValueError):
        fabric.lower("p2p", Torus((4,)), ("x",))    # rank-addressed


def test_rdma_bulk_put_get_pricing():
    from repro.core.rdma import RdmaEndpoint

    t = Torus((4,))
    src, dst = RdmaEndpoint(t, 0), RdmaEndpoint(t, 1)
    region = src.register(8 * 8192)
    dst_region = dst.register(8 * 8192)
    t1 = src.put_pages(1, region, [0, 1], page_nbytes=8192,
                       dst_endpoint=dst, dst_region=dst_region)
    assert t1 > 0 and dst.tlb.stats.accesses == 4     # 2 pages x 2 granules
    # more pages cost more; pages must fit the registered region
    assert src.put_pages(1, region, [0, 1, 2, 3], page_nbytes=8192) > \
        src.put_pages(1, region, [0], page_nbytes=8192)
    with pytest.raises(ValueError):
        src.put_pages(1, region, [7], page_nbytes=16384)   # straddles end
    with pytest.raises(KeyError):
        src.put_pages(1, dst_region, [0], page_nbytes=8192)  # not ours
    # GET: descriptor out + payload back, monotone in payload, and the
    # fault machinery reroutes/refuses it like any unicast
    g1 = src.get_time(1, 4096, region)
    g2 = src.get_time(1, 1 << 20, region)
    assert 0 < g1 < g2
    detour = src.get_time(1, 4096, region,
                          faults=fabric.FaultMap.normalized(links=[(0, 1)]))
    assert detour > 0
    with pytest.raises(fabric.UnroutableError):
        src.get_time(1, 4096, region,
                     faults=fabric.FaultMap.normalized(nodes=[1]))


# ---------------------------------------------------------------------------
# overlap engine: bucket lowering + overlap-aware cost model
# ---------------------------------------------------------------------------

def test_plan_buckets_reverse_order_covers_all_leaves():
    sizes = [100, 200, 3000, 50, 4000]
    plan = fabric.plan_buckets(sizes, 4096, itemsize=4)
    covered = [i for b in plan.buckets for i in b.leaves]
    assert sorted(covered) == list(range(len(sizes)))
    # readiness order: the LAST leaf's grads exist first in backward
    assert plan.buckets[0].leaves[0] == len(sizes) - 1
    assert plan.total_bytes == 4 * sum(sizes)
    # every bucket but the trailing remainder meets the size target
    for b in plan.buckets[:-1]:
        assert b.nbytes >= plan.bucket_bytes


def test_plan_buckets_validates():
    with pytest.raises(ValueError):
        fabric.plan_buckets([10], 0)
    with pytest.raises(ValueError):
        fabric.plan_buckets([], 1024)


def test_estimate_overlapped_accounts_for_fabric_busy_time():
    s = fabric.lower_reduce_scatter(Torus((8,)), ("x",), mean=True)
    plan = fabric.plan_buckets([1 << 16] * 16, 1 << 18)
    est = fabric.estimate_overlapped(s, plan, 0.01)
    busy = est.comm_s + est.overhead_s
    assert est.hidden_comm_s + est.exposed_comm_s == pytest.approx(busy)
    assert 0.0 <= est.efficiency <= 1.0
    assert est.total_s <= est.sequential_s + est.comm_s  # sane scale


def test_estimate_overlapped_compute_bound_hides_almost_all_comm():
    s = fabric.lower_reduce_scatter(Torus((8,)), ("x",), mean=True)
    plan = fabric.plan_buckets([1 << 16] * 64, 1 << 18)
    est = fabric.estimate_overlapped(s, plan, 10.0)
    # only the tail bucket (and issue gaps) can stay exposed
    assert est.efficiency > 0.9
    assert est.total_s == pytest.approx(est.compute_s, rel=0.05)


def test_estimate_overlapped_balanced_shape_cuts_quarter():
    """The Fig 1 regime: comm ~ compute -> >= 25% total-time reduction."""
    s = fabric.lower_reduce_scatter(Torus((8,)), ("x",), mean=True)
    plan = fabric.plan_buckets([1 << 18] * 32, 1 << 20)
    comm = fabric.estimate_overlapped(s, plan, 0.0).comm_s
    est = fabric.estimate_overlapped(s, plan, comm)  # compute == comm
    assert est.reduction >= 0.25
    assert est.total_s < est.sequential_s


def test_estimate_overlapped_single_slot_queue_never_faster():
    s = fabric.lower_reduce_scatter(Torus((8,)), ("x",), mean=True)
    plan = fabric.plan_buckets([1 << 14] * 128, 1 << 15)
    t1 = fabric.estimate_overlapped(s, plan, 1e-3, queue_depth=1).total_s
    t4 = fabric.estimate_overlapped(s, plan, 1e-3, queue_depth=4).total_s
    assert t1 >= t4


def test_estimate_overlapped_validates():
    s = fabric.lower_reduce_scatter(Torus((8,)), ("x",), mean=True)
    with pytest.raises(ValueError):
        fabric.estimate_overlapped(s, [100, 200], [0.1], queue_depth=2)
    with pytest.raises(ValueError):
        fabric.estimate_overlapped(s, [100], 0.1, queue_depth=0)


def test_bucket_grad_hook_rejects_wrong_schedules():
    ag = fabric.lower_all_gather(Torus((8,)), ("x",))
    plan = fabric.plan_buckets([10], 1024)
    with pytest.raises(ValueError):
        fabric.make_bucket_grad_hook(plan, ag)
    rs2 = fabric.lower_reduce_scatter(Torus((4, 2)), ("x", "y"))
    with pytest.raises(ValueError):
        fabric.make_bucket_grad_hook(plan, rs2)


# ---------------------------------------------------------------------------
# LO|FA|MO link-fault inference feeding the rewriter
# ---------------------------------------------------------------------------

def test_lofamo_link_fault_detected_as_link_not_node():
    sim = LofamoSim(Torus((4, 4)), wd_period=0.5)
    ev = sim.kill_link(1, 2)
    sim.run(3)
    assert sim.detected_links_at_master() == {(1, 2)}
    assert sim.detected_at_master() == set()  # both endpoints alive
    fm = fabric.fault_map_from_lofamo(sim)
    assert fm.dead_links == frozenset({(1, 2)})
    assert not fm.dead_nodes
    # awareness time is tracked for the link event like for node events
    assert ev.awareness_time is not None
    assert 0 < ev.awareness_time <= 2 * 0.5 + 1e-2


def test_lofamo_node_fault_still_node_not_link():
    sim = LofamoSim(Torus((4, 4)), wd_period=0.5)
    sim.kill_node(5)
    sim.run(3)
    assert 5 in sim.detected_at_master()
    assert sim.detected_links_at_master() == set()


def test_lofamo_fault_map_drives_rewrite():
    sim = LofamoSim(Torus((8,)), wd_period=0.5)
    sim.kill_link(3, 4)
    sim.run(3)
    sched = fabric.lower_all_reduce(Torus((8,)), ("x",))
    r = fabric.rewrite(sched, fabric.fault_map_from_lofamo(sim))
    assert r.max_hops == 7


# ---------------------------------------------------------------------------
# numeric equivalence on 1D/2D/3D tori (8 forced host devices)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fabric_multidevice_equivalence():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "fabric_checks.py")],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, \
        f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    assert "ALL FABRIC CHECKS PASSED" in proc.stdout
