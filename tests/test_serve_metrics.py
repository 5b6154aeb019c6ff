"""Step-clock serving metrics: histograms, TTFT/per-token accounting, Jain.

Everything here is pure host-side Python (`repro.serve.metrics`) — no
model, no jax — so this file is the fail-fast front of the CI service-
layer lane. The recorder's clock is the scheduler step counter, which is
what makes the latency numbers bit-deterministic and CI-gateable; the
tests drive it exactly the way `ContinuousBatcher` does (tick at the top
of each step, then events).
"""
import pytest

from repro.serve.metrics import Histogram, ServeMetrics, jain


# ---------------------------------------------------------------- histogram

def test_histogram_nearest_rank_percentiles():
    h = Histogram()
    for v in [5, 1, 4, 2, 3]:  # order must not matter
        h.add(v)
    assert h.percentile(50) == 3   # rank ceil(5*.5)=3 -> 3rd smallest
    assert h.percentile(99) == 5   # rank ceil(5*.99)=5
    assert h.percentile(100) == 5
    assert h.percentile(1) == 1    # rank max(1, ceil(.05)) = 1
    assert h.summary() == {"n": 5, "p50": 3, "p99": 5, "mean": 3.0,
                           "max": 5}


def test_histogram_single_sample_and_empty():
    h = Histogram()
    assert h.percentile(50) is None
    assert h.summary()["n"] == 0 and h.summary()["p99"] is None
    h.add(7)
    assert h.percentile(50) == 7 and h.percentile(99) == 7
    assert len(h) == 1


def test_histogram_rejects_out_of_range_p():
    h = Histogram()
    h.add(1)
    for p in (0, -1, 101):
        with pytest.raises(ValueError, match="must be in"):
            h.percentile(p)


# --------------------------------------------------------------------- jain

def test_jain_known_values():
    assert jain([1, 1, 1]) == pytest.approx(1.0)
    assert jain([16, 8]) == pytest.approx(0.9)  # (24^2)/(2*320)
    # one tenant got everything out of n: index -> 1/n
    assert jain([10, 0, 0, 0]) == pytest.approx(0.25)
    assert jain([]) == 1.0       # no tenants: vacuously fair
    assert jain([0, 0]) == 1.0   # no service at all: nothing unfair yet


# ------------------------------------------------------------ serve metrics

def test_ttft_counts_queue_wait_and_tpl_counts_gaps():
    """The scenario the recorder exists for: a request that waited in the
    queue pays its wait in TTFT, and a slot that sits out steps pays the
    gap in per-token latency."""
    m = ServeMetrics()
    m.tick()                       # step 1
    m.on_submit(0)
    m.on_submit(1)                 # waits behind rid 0
    m.tick()                       # step 2
    m.on_first_token(0, "a")       # TTFT = 2 - 1 = 1
    m.tick()                       # step 3
    m.on_token(0, "a")             # gap 1
    m.tick()                       # step 4
    m.on_first_token(1, "a")       # TTFT = 4 - 1 = 3 (queue wait included)
    m.tick()                       # step 5
    m.tick()                       # step 6 (rid 0 sat steps 4-5 out)
    m.on_token(0, "a")             # gap 6 - 3 = 3: idle steps are paid
    m.on_token(1, "a")             # gap 6 - 4 = 2
    assert sorted(m.ttft.samples) == [1, 3]
    assert sorted(m.tpl.samples) == [1, 2, 3]
    assert m.tenant_tokens == {"a": 5}
    s = m.summary()
    assert s["steps"] == 6 and s["ttft_n"] == 2 and s["tpl_n"] == 3
    assert s["ttft_p99"] == 3 and s["tpl_p50"] == 2


def test_reject_and_error_do_not_pollute_latency():
    m = ServeMetrics()
    m.tick()
    m.on_submit(0)
    m.on_reject(0)         # depth-cap rejection: no TTFT sample ever
    m.on_submit(1)
    m.tick()
    m.on_first_token(1)
    m.on_error(1)          # faulted mid-flight: no further tpl samples
    m.tick()
    m.on_token(1)          # stale event after error: gap has no baseline
    assert m.rejected == 1 and m.errored == 1
    assert len(m.ttft) == 1 and len(m.tpl) == 0
    s = m.summary()
    assert s["rejected"] == 1 and s["errored"] == 1


def test_fairness_normalizes_by_weight():
    m = ServeMetrics()
    m.tick()
    for _ in range(30):
        m.on_token(0, "heavy")
    for _ in range(10):
        m.on_token(1, "light")
    # 30 vs 10 tokens at weights 3:1 is exactly proportional service
    assert m.fairness({"heavy": 3.0, "light": 1.0}) == pytest.approx(1.0)
    # unweighted, the same split is lopsided
    assert m.fairness() == pytest.approx(jain([30, 10]))
    assert ServeMetrics().fairness({"a": 2.0}) == 1.0  # nothing served yet
