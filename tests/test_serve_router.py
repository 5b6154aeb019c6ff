"""Tenant-aware admission routing: policy order, fairness, depth caps.

Pure host-side (`repro.serve.router`) — no model, no jax. The router's
contract has three parts, each pinned here:

* **deque compatibility** — truthiness/len/iter/[0]/popleft behave like
  the FIFO deque it replaced, so every `ContinuousBatcher` drain loop and
  backpressure path works unchanged (and ``[0]`` then ``popleft()`` agree
  on the head: backpressure re-offers the SAME request);
* **policy order** — fifo is arrival order; priority is strict by weight
  (and a later high-priority arrival preempts a waiting low-priority
  head); wfq shares admitted token budget proportionally to weights and
  never starves anyone — fuzzed over generated multi-tenant backlogs with
  Jain's index as the acceptance measure, mirroring the bench gate;
* **depth caps** — per-tenant overload rejects at push with a structured
  ``RequestError(stage="admit")`` naming the tenant and cap (operational
  backpressure is data, not an exception).
"""
import numpy as np
import pytest

from repro.serve.batching import Request
from repro.serve.metrics import jain
from repro.serve.router import AdmissionRouter, request_cost

from _hypothesis_compat import given, settings, strategies as st


def _req(rid, tenant="default", plen=4, n_new=2):
    return Request(rid, np.arange(plen, dtype=np.int32), n_new=n_new,
                   tenant=tenant)


def _drain(r):
    out = []
    while r:
        out.append(r.popleft())
    return out


# ------------------------------------------------------------ construction

def test_rejects_bad_parameters():
    with pytest.raises(ValueError, match="unknown router policy"):
        AdmissionRouter(policy="lifo")
    with pytest.raises(ValueError, match="quantum"):
        AdmissionRouter(quantum=0)
    with pytest.raises(ValueError, match="max_queue_per_tenant"):
        AdmissionRouter(max_queue_per_tenant=0)


# -------------------------------------------------------- deque-compatible

def test_deque_surface_matches_fifo_semantics():
    r = AdmissionRouter()
    assert not r and len(r) == 0
    with pytest.raises(IndexError, match="empty"):
        r[0]
    with pytest.raises(IndexError, match="empty"):
        r.popleft()
    reqs = [_req(i, t) for i, t in enumerate("abcab")]
    for q in reqs:
        assert r.push(q) is None
    assert r and len(r) == 5
    assert [q.rid for q in r] == [0, 1, 2, 3, 4]  # iteration: arrival order
    with pytest.raises(IndexError, match="only the policy head"):
        r[1]
    assert r[0] is reqs[0] and r[0] is r.popleft()  # peek == pop head
    assert [q.rid for q in _drain(r)] == [1, 2, 3, 4]
    assert not r and len(r) == 0 and list(r) == []


def test_fifo_is_tenant_blind_arrival_order():
    r = AdmissionRouter(policy="fifo", weights={"vip": 100.0})
    for i, t in enumerate(["free", "vip", "free", "vip"]):
        r.push(_req(i, t))
    assert [q.rid for q in _drain(r)] == [0, 1, 2, 3]


# ------------------------------------------------------------- priority

def test_priority_serves_heaviest_tenant_first_fifo_within_class():
    r = AdmissionRouter(policy="priority", weights={"gold": 3, "bronze": 1})
    order = ["bronze", "gold", "bronze", "gold", "silverless"]  # w=1 default
    for i, t in enumerate(order):
        r.push(_req(i, t))
    # gold (w=3) first in arrival order, then the three w=1 in arrival order
    assert [q.rid for q in _drain(r)] == [1, 3, 0, 2, 4]


def test_priority_late_arrival_preempts_waiting_head():
    """The head is policy-fresh until popped: a high-priority request that
    arrives while a low-priority head waits (e.g. under page-pool
    backpressure) is served first once admission resumes."""
    r = AdmissionRouter(policy="priority", weights={"gold": 2})
    r.push(_req(0, "bronze"))
    assert r[0].rid == 0          # bronze is the head ...
    r.push(_req(1, "gold"))
    assert r[0].rid == 1          # ... until gold arrives
    assert [q.rid for q in _drain(r)] == [1, 0]


# ------------------------------------------------------------------ wfq

def test_wfq_peek_pop_agree_and_deficits_charge_once():
    r = AdmissionRouter(policy="wfq", weights={"a": 2, "b": 1})
    for i, t in enumerate("abab"):
        r.push(_req(i, t))
    for _ in range(4):
        head = r[0]
        assert r[0] is head        # repeated peeks don't advance DRR state
        assert r.popleft() is head


def test_wfq_proportional_service_on_backlog():
    """Two always-backlogged tenants at weights 3:1 with equal-cost
    requests: a service window's admitted counts track the weights."""
    r = AdmissionRouter(policy="wfq", weights={"heavy": 3, "light": 1},
                        quantum=8.0)
    rid = 0
    for _ in range(40):
        for t in ("heavy", "light"):
            r.push(_req(rid, t, plen=6, n_new=2))  # cost 8 each
            rid += 1
    window = [r.popleft().tenant for _ in range(40)]
    served = {t: window.count(t) for t in ("heavy", "light")}
    # 3:1 on 40 pops is 30/10; DRR rounding can wobble by a request
    assert abs(served["heavy"] - 30) <= 1
    assert served["heavy"] + served["light"] == 40
    fairness = jain([served["heavy"] / 3.0, served["light"] / 1.0])
    assert fairness > 0.99


def test_wfq_emptied_queue_forfeits_deficit():
    """Classic DRR: a tenant that drains its queue cannot bank deficit
    and burst later — it restarts from zero when traffic returns."""
    r = AdmissionRouter(policy="wfq", weights={"a": 5, "b": 1}, quantum=100)
    r.push(_req(0, "a"))
    r.popleft()
    assert r._deficit["a"] == 0.0  # not 100*5 - cost
    # returning traffic competes from scratch
    r.push(_req(1, "b"))
    r.push(_req(2, "a"))
    assert len(_drain(r)) == 2


@settings(max_examples=60, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_wfq_fuzz_no_starvation_and_conservation(seed):
    """Generated multi-tenant backlogs (the acceptance fuzz): every
    submitted request is served exactly once (conservation), and no
    tenant starves — DRR's guarantee is a BOUNDED first-service delay:
    tenant t needs at most ceil(maxcost / (quantum*w_t)) pointer visits
    to cover its head, and between two visits every other tenant can
    spend at most its per-visit top-up plus its carried deficit
    (< quantum*w_j + maxcost tokens). The bound holds for every seed,
    unlike window-count checks, which DRR's quantum-scale service bursts
    legitimately violate."""
    import math

    rng = np.random.default_rng(seed)
    n_tenants = int(rng.integers(2, 5))
    tenants = [f"t{i}" for i in range(n_tenants)]
    weights = {t: float(rng.integers(1, 5)) for t in tenants}
    quantum = float(rng.integers(2, 9))
    r = AdmissionRouter(policy="wfq", weights=weights, quantum=quantum)
    rid, maxcost = 0, 0
    per_tenant = int(rng.integers(8, 16))
    for _ in range(per_tenant):
        for t in tenants:
            q = _req(rid, t, plen=int(rng.integers(1, 9)),
                     n_new=int(rng.integers(1, 5)))
            maxcost = max(maxcost, request_cost(q))
            r.push(q)
            rid += 1
    first_seen, spent, order = {}, 0, []
    while r:
        q = r.popleft()
        order.append(q.rid)
        first_seen.setdefault(q.tenant, spent)
        spent += request_cost(q)
    assert sorted(order) == list(range(rid))  # conservation, exactly once
    for t in tenants:
        visits = math.ceil(maxcost / (quantum * weights[t]))
        bound = visits * sum(quantum * weights[j] + maxcost
                             for j in tenants if j != t)
        assert first_seen[t] <= bound, (
            f"seed={seed}: tenant {t} first served after {first_seen[t]} "
            f"tokens, DRR delay bound is {bound}")


# -------------------------------------------------------------- depth caps

def test_depth_cap_rejects_with_structured_error():
    r = AdmissionRouter(max_queue_per_tenant=2)
    assert r.push(_req(0, "a")) is None
    assert r.push(_req(1, "a")) is None
    err = r.push(_req(2, "a"))
    assert err is not None and err.stage == "admit" and err.rid == 2
    assert "'a'" in err.reason and "cap (2)" in err.reason
    # caps are per tenant: another tenant is unaffected
    assert r.push(_req(3, "b")) is None
    assert r.rejected == 1 and len(r) == 3
    # popping frees headroom
    r.popleft()
    assert r.push(_req(4, "a")) is None
