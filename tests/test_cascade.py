"""Confidence-routed cascade contract (CPU, tier-1 fast): calibration
is deterministic for a seeded sample and fails CLOSED on thin data, a
confident front tier answers while low confidence escalates to a
bit-identical big-only answer, an escalated request carries its
REMAINING deadline (never a fresh budget), a version swap of either
tier drops the calibration, and always-big QoS tenants bypass the
front tier entirely.

Most tests drive ``CascadeRouter`` over a fake plane (synchronous
futures, recorded deadlines) — routing correctness is about the
decision logic, not real engines.  One real-plane test runs LeNet-5
(front, confidence epilogue fused) against LeNet5Big (big, dense
logits) at random init to pin the end-to-end row shapes.
"""

import time
from concurrent.futures import Future

import numpy as np
import pytest

from deep_vision_tpu.serve.admission import Shed, TenantQoS
from deep_vision_tpu.serve.cascade import CascadeRouter, CascadeSpec
from deep_vision_tpu.serve.models import AgreementHistogram
from deep_vision_tpu.serve.workloads import ClassifyWorkload

pytestmark = pytest.mark.models


def _front_row(cls=3, prob=0.9):
    """A confidence-epilogue row as the front engine scatters it."""
    return {"topk_class": np.array([cls, 1, 2], np.int32),
            "topk_prob": np.array([prob, 0.05, 0.02], np.float32),
            "topk_logit": np.array([5.0, 1.0, 0.5], np.float32)}


def _big_row(cls=3, n=10, seed=0):
    """Dense logits with argmax ``cls`` — what the big tier serves."""
    logits = np.random.RandomState(seed).randn(n).astype(np.float32)
    logits[cls] = logits.max() + 3.0
    return logits


class FakePlane:
    """Synchronous stand-in for ModelControlPlane.submit: resolves each
    future inline from a per-model row (value, callable, or exception)
    and records every ``(name, deadline_ms)`` for deadline assertions."""

    def __init__(self, rows, delay_s=0.0):
        self.rows = rows
        self.delay_s = delay_s
        self.sleep = time.sleep  # a test with its own clock replaces it
        self.calls = []
        self.listeners = []

    def add_version_listener(self, fn):
        self.listeners.append(fn)

    def submit(self, name, image, deadline_ms=None, span=None):
        self.calls.append((name, deadline_ms))
        if self.delay_s:
            self.sleep(self.delay_s)
        fut = Future()
        row = self.rows[name]
        if callable(row):
            row = row(image)
        if isinstance(row, Exception):
            fut.set_exception(row)
        else:
            fut.set_result(row)
        return fut

    def resolve(self, name):
        raise KeyError(name)

    def canary_active(self, name):
        return False


def _router(rows, *, delay_s=0.0, threshold=None, **spec_kw):
    spec_kw.setdefault("sample_period", 1000)  # no sampling by default
    spec = CascadeSpec("small", "large", **spec_kw)
    plane = FakePlane(dict(rows), delay_s=delay_s)
    router = CascadeRouter(plane, spec)
    if threshold is not None:
        # seed a calibration directly: every sample at the threshold's
        # bin agreed, enough of them to clear min_sample
        for _ in range(max(spec.min_sample, 1)):
            router.hist.record(threshold, True)
        router._recalibrate()
        assert router.threshold is not None
    return router, plane


# -- calibration math ------------------------------------------------------


def test_histogram_threshold_deterministic_seeded_sample():
    """Seeded synthetic sample: agreement rises with confidence, and
    the threshold lands exactly on the smallest bin edge whose suffix
    clears the floor — same sample, same answer, every run."""
    hist = AgreementHistogram(bins=10)
    rng = np.random.RandomState(42)
    for conf in rng.uniform(0.0, 1.0, size=2000):
        # agreement probability grows with confidence: sure-above-0.7,
        # coin-flip-below — the shape a real cascade sample has
        agreed = bool(conf >= 0.7 or rng.uniform() < 0.5)
        hist.record(float(conf), agreed)
    thr = hist.threshold(min_agreement=0.95, min_sample=100)
    assert thr == pytest.approx(0.7)
    # a laxer floor admits more of the distribution (smaller threshold);
    # a stricter one admits less or nothing — monotone in the floor
    lax = hist.threshold(min_agreement=0.60, min_sample=100)
    assert lax is not None and lax <= thr
    assert hist.threshold(min_agreement=1.01, min_sample=100) is None


def test_histogram_fails_closed_on_thin_sample():
    hist = AgreementHistogram(bins=10)
    for _ in range(50):
        hist.record(0.95, True)   # bin 9: perfect
    for _ in range(49):
        hist.record(0.55, False)  # bin 5: hopeless
    # 99 samples < min_sample: fail closed regardless of agreement
    assert hist.threshold(min_agreement=0.9, min_sample=100) is None
    hist.record(0.55, False)
    # thick enough: bin 9 qualifies, the empty bins 6-8 never extend
    # the threshold into unobserved territory, and the disagreeing
    # bin 5 can't qualify
    assert hist.threshold(min_agreement=0.9, min_sample=100) == \
        pytest.approx(0.9)
    hist.reset()
    assert hist.threshold(min_agreement=0.9, min_sample=1) is None
    assert hist.stats()["samples"] == 0


# -- routing ---------------------------------------------------------------


def test_uncalibrated_routes_everything_big():
    """Fail closed: before min_sample dual-runs, no request may stop at
    the front tier."""
    router, plane = _router({"small": _front_row(), "large": _big_row()})
    for _ in range(20):
        tier, row = router.infer(np.zeros((4, 4, 1), np.float32))
        assert tier == "big"
        np.testing.assert_array_equal(row, plane.rows["large"])
    assert all(name == "large" for name, _ in plane.calls)
    st = router.stats()
    assert st["calibrated"] is False and st["threshold"] is None
    assert st["served"] == {"front": 0, "big": 20}
    assert st["escalation_rate"] is None  # front judged nothing yet


def test_confident_front_serves_lowconf_escalates_bit_identical():
    router, plane = _router(
        {"small": _front_row(prob=0.9), "large": _big_row()},
        threshold=0.5)
    x = np.zeros((4, 4, 1), np.float32)
    tier, row = router.infer(x)
    assert tier == "front" and isinstance(row, dict)
    assert ClassifyWorkload.top1(row) == (3, pytest.approx(0.9))

    # drop the front's confidence below threshold: the answer must be
    # the big tier's row, bit-identical to a big-only submission
    plane.rows["small"] = _front_row(prob=0.2)
    tier, row = router.infer(x)
    assert tier == "big"
    assert row.tobytes() == plane.rows["large"].tobytes()
    st = router.stats()
    assert st["served"] == {"front": 1, "big": 1}
    assert st["escalations"] == 1 and st["escalated_lowconf"] == 1
    assert st["escalation_rate"] == pytest.approx(0.5)


def test_front_error_escalates():
    """A front-tier Shed (or raise) never reaches the client — the big
    tier owns the contract."""
    router, plane = _router(
        {"small": Shed("queue_full", "front full"), "large": _big_row()},
        threshold=0.5)
    tier, row = router.infer(np.zeros((4, 4, 1), np.float32))
    assert tier == "big" and not isinstance(row, Shed)
    assert router.stats()["escalated_error"] == 1

    plane.rows["small"] = RuntimeError("front died")
    tier, row = router.infer(np.zeros((4, 4, 1), np.float32))
    assert tier == "big" and not isinstance(row, Shed)
    assert router.stats()["escalated_error"] == 2


def test_escalation_preserves_original_deadline():
    """The escalated submit carries deadline − front-elapsed, never a
    fresh budget; a front attempt that ate the whole budget sheds
    instead of escalating."""
    router, plane = _router(
        {"small": _front_row(prob=0.2), "large": _big_row()},
        threshold=0.5, delay_s=0.02)
    tier, _ = router.infer(np.zeros((4, 4, 1), np.float32),
                           deadline_ms=500.0)
    assert tier == "big"
    (fname, fdl), (bname, bdl) = plane.calls
    assert (fname, fdl) == ("small", 500.0)
    assert bname == "large" and 0.0 < bdl <= 500.0 - 20.0

    # budget thinner than the front attempt: no big submit at all
    plane.calls.clear()
    tier, row = router.infer(np.zeros((4, 4, 1), np.float32),
                             deadline_ms=5.0)
    assert tier == "big" and isinstance(row, Shed)
    assert row.reason == "deadline"
    assert [name for name, _ in plane.calls] == ["small"]
    assert router.stats()["escalated_shed"] == 1


def test_sampling_calibrates_then_version_swap_resets():
    """Every sample_period-th request dual-runs both tiers; once the
    sample is thick enough the threshold appears, and a version swap of
    either tier drops it (fail closed again)."""
    router, plane = _router(
        {"small": _front_row(cls=3, prob=0.97), "large": _big_row(cls=3)},
        sample_period=1, min_sample=10, min_agreement=0.9)
    x = np.zeros((4, 4, 1), np.float32)
    for _ in range(10):
        tier, _ = router.infer(x)
        assert tier == "big"  # sampled requests answer from big
    st = router.stats()
    assert st["samples"] == 10 and st["calibrated"] is True
    assert st["threshold"] == pytest.approx(0.95)
    assert st["agreement"] == pytest.approx(1.0)

    assert len(plane.listeners) == 1
    plane.listeners[0]("unrelated-model")
    assert router.threshold is not None  # foreign swap: no reset
    plane.listeners[0]("small")
    st = router.stats()
    assert st["calibrated"] is False and st["resets"] == 1
    assert st["agreement_bins"]["samples"] == 0


def test_disagreeing_sample_never_calibrates():
    """Front and big that never agree: no confidence level clears the
    floor, so the cascade stays all-big forever."""
    router, _ = _router(
        {"small": _front_row(cls=1, prob=0.99), "large": _big_row(cls=3)},
        sample_period=1, min_sample=5, min_agreement=0.9)
    x = np.zeros((4, 4, 1), np.float32)
    for _ in range(20):
        tier, _ = router.infer(x)
        assert tier == "big"
    st = router.stats()
    assert st["calibrated"] is False and st["samples"] == 20


def test_force_big_bypasses_front():
    """Always-big QoS tenants: force_big never touches the front tier,
    calibrated or not."""
    router, plane = _router(
        {"small": _front_row(prob=0.99), "large": _big_row()},
        threshold=0.1)
    tier, _ = router.infer(np.zeros((4, 4, 1), np.float32),
                           force_big=True)
    assert tier == "big"
    assert [name for name, _ in plane.calls] == ["large"]
    assert router.stats()["forced_big"] == 1


def test_qos_always_big_spec_parses():
    qos = TenantQoS.parse("premium:rate=0,always_big=1,tenants=acme;"
                          "standard:rate=100;default=standard")
    assert qos.class_of("acme").always_big is True
    assert qos.class_of("someone").always_big is False
    st = qos.stats()
    assert st["premium"]["always_big"] is True
    assert st["standard"]["always_big"] is False


def test_serves_only_big_name():
    router, _ = _router({"small": _front_row(), "large": _big_row()})
    assert router.serves("large") and not router.serves("small")
    with pytest.raises(ValueError):
        CascadeSpec("same", "same")
    with pytest.raises(ValueError):
        CascadeSpec.parse("no-colon-here")


def test_respond_identical_for_escalated_and_big_only():
    """The full client-visible JSON of an escalated answer matches a
    big-only answer byte for byte — the quality contract the big name
    promises."""
    import json

    big = _big_row()
    router, _ = _router({"small": _front_row(prob=0.1), "large": big},
                        threshold=0.5)
    _, escalated = router.infer(np.zeros((4, 4, 1), np.float32))

    class _M:
        name = "large"

    w = ClassifyWorkload()
    a = json.dumps(w.respond(_M(), {}, escalated), sort_keys=True)
    b = json.dumps(w.respond(_M(), {}, big), sort_keys=True)
    assert a == b


# -- N-tier chains ---------------------------------------------------------


def _mid_row(cls=3, prob=0.9):
    return _front_row(cls=cls, prob=prob)


def _router3(rows, *, delay_s=0.0, thresholds=(None, None), **spec_kw):
    """3-tier small:mid:large router over a FakePlane; ``thresholds``
    seeds hop 0 / hop 1 calibrations directly."""
    spec_kw.setdefault("sample_period", 1000)
    spec = CascadeSpec("small", "mid", "large", **spec_kw)
    plane = FakePlane(dict(rows), delay_s=delay_s)
    router = CascadeRouter(plane, spec)
    for hop, thr in zip(router.hops, thresholds):
        if thr is not None:
            for _ in range(max(spec.min_sample, 1)):
                hop.hist.record(thr, True)
            router._recalibrate(hop)
            assert hop.threshold is not None
    return router, plane


def test_three_tier_tokens_and_mid_serving():
    """A calibrated middle hop answers with token "t1"; hop 0 low
    confidence escalates one hop, not straight to big."""
    router, plane = _router3(
        {"small": _front_row(prob=0.2), "mid": _mid_row(prob=0.9),
         "large": _big_row()},
        thresholds=(0.5, 0.5))
    tier, row = router.infer(np.zeros((4, 4, 1), np.float32))
    assert tier == "t1" and isinstance(row, dict)
    assert [name for name, _ in plane.calls] == ["small", "mid"]
    st = router.stats()
    assert st["served"] == {"front": 0, "t1": 1, "big": 0}
    assert st["tiers"] == ["small", "mid", "large"]
    assert [h["token"] for h in st["hops"]] == ["front", "t1"]


def test_uncalibrated_hop_escalates_through_without_running_tier():
    """Fail closed per hop: an uncalibrated middle hop is SKIPPED — its
    tier never runs, the request proceeds down the chain."""
    router, plane = _router3(
        {"small": _front_row(prob=0.2), "mid": _mid_row(prob=0.99),
         "large": _big_row()},
        thresholds=(0.5, None))
    tier, row = router.infer(np.zeros((4, 4, 1), np.float32))
    assert tier == "big"
    assert [name for name, _ in plane.calls] == ["small", "large"]
    assert row.tobytes() == plane.rows["large"].tobytes()

    # fully uncalibrated chain: only big runs
    router2, plane2 = _router3(
        {"small": _front_row(), "mid": _mid_row(),
         "large": _big_row()})
    tier, _ = router2.infer(np.zeros((4, 4, 1), np.float32))
    assert tier == "big"
    assert [name for name, _ in plane2.calls] == ["large"]


def test_twice_escalated_request_never_exceeds_original_budget(monkeypatch):
    """Satellite: a request escalated through BOTH cheap tiers submits
    to each next tier with strictly shrinking remainders of its ONE
    original deadline — and sheds when the chain eats the budget.  The
    router reads an injected clock that only a tier's 20 ms of work
    advances, so the remainders are exact and no scheduler delay on a
    loaded host can eat a budget."""
    from deep_vision_tpu.serve import cascade

    class Clock:
        """Stands in for the ``time`` module where the router reads it."""
        now = 100.0
        time = staticmethod(time.time)

        def monotonic(self):
            return self.now

        def sleep(self, seconds):
            self.now += seconds

    clock = Clock()
    monkeypatch.setattr(cascade, "time", clock)
    router, plane = _router3(
        {"small": _front_row(prob=0.1), "mid": _mid_row(prob=0.1),
         "large": _big_row()},
        thresholds=(0.5, 0.5), delay_s=0.02)
    plane.sleep = clock.sleep
    tier, _ = router.infer(np.zeros((4, 4, 1), np.float32),
                           deadline_ms=500.0)
    assert tier == "big"
    (n0, d0), (n1, d1), (n2, d2) = plane.calls
    assert (n0, d0) == ("small", 500.0)  # hop 0 sees the EXACT budget
    assert n1 == "mid" and n2 == "large"
    # each hop burned its 20ms of the same 500ms budget
    assert d1 == pytest.approx(480.0) and d2 == pytest.approx(460.0)
    assert router.stats()["escalations"] == 2

    # budget dies mid-chain (30ms: one hop fits, two do not): big is
    # never submitted, the client gets a deadline Shed
    plane.calls.clear()
    tier, row = router.infer(np.zeros((4, 4, 1), np.float32),
                             deadline_ms=30.0)
    assert tier == "big" and isinstance(row, Shed)
    assert row.reason == "deadline"
    assert [(name, round(d)) for name, d in plane.calls] == [
        ("small", 30), ("mid", 10)]
    assert router.stats()["escalated_shed"] == 1


def test_version_swap_resets_only_its_hop_big_resets_all():
    """A mid-tier swap drops hop 1's calibration only; a big swap drops
    every hop (big is every hop's comparison target)."""
    router, plane = _router3(
        {"small": _front_row(), "mid": _mid_row(), "large": _big_row()},
        thresholds=(0.5, 0.7))
    plane.listeners[0]("mid")
    assert router.hops[0].threshold is not None
    assert router.hops[1].threshold is None
    # re-seed hop 1, then swap big: both hops drop
    for _ in range(200):
        router.hops[1].hist.record(0.7, True)
    router._recalibrate(router.hops[1])
    plane.listeners[0]("large")
    assert router.hops[0].threshold is None
    assert router.hops[1].threshold is None


def test_ledger_roundtrip_and_any_tier_digest_rejection(tmp_path):
    """Satellite: the ledger key covers ALL tier digests — a restore
    adopts a hop's calibration only when EVERY live tier matches, so a
    mid-tier reload while down rejects the record."""

    class DigestPlane(FakePlane):
        def __init__(self, rows, digests):
            super().__init__(rows)
            self.digests = digests

        def resolve(self, name):
            m = type("M", (), {})()
            m.params_digest = self.digests[name]
            return m

    rows = {"small": _front_row(), "mid": _mid_row(),
            "large": _big_row()}
    digests = {"small": "d0", "mid": "d1", "large": "d2"}
    spec = CascadeSpec("small", "mid", "large", sample_period=1000,
                       min_sample=10)
    plane = DigestPlane(rows, dict(digests))
    router = CascadeRouter(plane, spec, root=str(tmp_path))
    assert router.params_digest() == "d0+d1+d2"
    for _ in range(10):
        router.hops[0].hist.record(0.8, True)
    router._recalibrate(router.hops[0])
    for _ in range(10):
        router.hops[1].hist.record(0.6, True)
    router._recalibrate(router.hops[1])

    # same digests: both hops restore, thresholds re-derived
    r2 = CascadeRouter(DigestPlane(rows, dict(digests)), spec,
                       root=str(tmp_path))
    assert r2.restored is True
    assert r2.hops[0].threshold == pytest.approx(0.8)
    assert r2.hops[1].threshold == pytest.approx(0.6)

    # ONE tier (the middle one) reloaded while down: every hop's
    # record is stale — nothing restores
    changed = dict(digests, mid="d1-reloaded")
    r3 = CascadeRouter(DigestPlane(rows, changed), spec,
                       root=str(tmp_path))
    assert r3.restored is False
    assert r3.hops[0].threshold is None
    assert r3.hops[1].threshold is None

    # a persisted reset for one hop wins over its older calibration
    router._on_version_swap("mid")
    r4 = CascadeRouter(DigestPlane(rows, dict(digests)), spec,
                       root=str(tmp_path))
    assert r4.hops[0].threshold == pytest.approx(0.8)
    assert r4.hops[1].threshold is None


def test_per_class_thresholds_and_fail_closed_class():
    """Per-class axis: a class with its own qualifying sample uses its
    own threshold; a measured-bad class fails CLOSED (escalates at any
    confidence) instead of riding the pooled threshold."""
    router, plane = _router(
        {"small": _front_row(cls=3, prob=0.9), "large": _big_row()},
        per_class=True, class_min_sample=20, min_sample=20,
        min_agreement=0.9)
    hop = router.hops[0]
    # class 3 agrees from 0.62 up; class 1 NEVER agrees; class 7 thin
    for _ in range(30):
        hop.hist.record(0.62, True, cls=3)
    for _ in range(30):
        hop.hist.record(0.9, False, cls=1)
    for _ in range(5):
        hop.hist.record(0.9, True, cls=7)
    router._recalibrate()
    assert hop.class_thresholds[3] == pytest.approx(0.60)
    assert hop.class_thresholds[1] is None  # fail-closed class
    assert 7 not in hop.class_thresholds    # thin → pooled fallback

    # class 3 at 0.9: served by the front tier
    tier, _ = router.infer(np.zeros((4, 4, 1), np.float32))
    assert tier == "front"
    # class 1 at 0.9 (above any pooled threshold): still escalates
    plane.rows["small"] = _front_row(cls=1, prob=0.97)
    tier, _ = router.infer(np.zeros((4, 4, 1), np.float32))
    assert tier == "big"
    st = router.stats()
    assert st["hops"][0]["class_thresholds"]["3"] == pytest.approx(0.6)


def test_detect_cascade_rule_signal_and_agreement():
    """The detect rule: confidence = best valid device-decoded score,
    class = its label; agreement = the greedy-IoU verdict; decoded-row
    shape errors are (None, None) → escalate."""
    from deep_vision_tpu.serve.workloads import DetectWorkload

    rule = DetectWorkload().cascade_rule()

    def det_row(scores, classes, boxes=None):
        k = len(scores)
        b = boxes if boxes is not None else \
            np.tile(np.array([0.1, 0.1, 0.3, 0.3], np.float32), (k, 1))
        return {"boxes": np.asarray(b, np.float32),
                "scores": np.asarray(scores, np.float32),
                "classes": np.asarray(classes, np.int64),
                "valid": (np.asarray(scores) > 0).astype(np.float32)}

    cls, conf = rule.signal(det_row([0.9, 0.4, 0.0], [2, 5, 0]))
    assert cls == 2 and conf == pytest.approx(0.9)
    # empty detection is a SIGNAL (confidently nothing), not an error
    cls, conf = rule.signal(det_row([0.0, 0.0], [0, 0]))
    assert cls is None and conf == 0.0
    # a dense (non-decoded) row has no signal: escalate
    assert rule.signal(np.zeros((13, 13, 18))) == (None, None)

    a = det_row([0.9], [2])
    assert rule.agree(a, a) is True
    far = det_row([0.9], [2],
                  boxes=[[0.7, 0.7, 0.9, 0.9]])
    assert rule.agree(a, far) is False


def test_inner_hop_calibrates_against_final_tier():
    """Each hop dual-runs its OWN tier against the final tier on the
    traffic that reaches it: a front tier the big model keeps
    contradicting never calibrates (fail-closed), while the middle
    tier calibrates on the escalated-through stream and starts
    serving."""
    router, plane = _router3(
        {"small": _front_row(cls=2, prob=0.97),   # big says 3: disagree
         "mid": _mid_row(cls=3, prob=0.97),       # agrees with big
         "large": _big_row(cls=3)},
        sample_period=2, min_sample=3, min_agreement=0.9)
    x = np.zeros((4, 4, 1), np.float32)
    tiers = [router.infer(x)[0] for _ in range(20)]
    st = router.stats()
    # hop 0 ticks every request, sampling half of it — and every
    # sample disagrees, so it stays uncalibrated
    assert st["hops"][0]["samples"] == 10
    assert not st["hops"][0]["calibrated"]
    assert st["hops"][0]["agreement"] == pytest.approx(0.0)
    # the other half escalates THROUGH to hop 1, which samples ITS
    # even ticks against big, calibrates, and begins serving "t1"
    assert st["hops"][1]["samples"] == 5
    assert st["hops"][1]["calibrated"]
    assert st["served"]["t1"] >= 1 and "t1" in tiers
    # nothing was ever answered by the measured-bad front tier
    assert st["served"]["front"] == 0


# -- real plane ------------------------------------------------------------


def test_real_plane_front_epilogue_and_escalation(tmp_path):
    """LeNet-5 (front, cascade_topk=3 → fused confidence epilogue)
    against LeNet5Big (big, dense logits) on a real control plane:
    front rows are top-K dicts, big rows are dense logits bit-identical
    to big-only serving, and both shapes flow through respond()."""
    from deep_vision_tpu.serve.admission import AdmissionController
    from deep_vision_tpu.serve.engine import BatchingEngine
    from deep_vision_tpu.serve.models import ModelControlPlane
    from deep_vision_tpu.serve.registry import ModelRegistry

    reg = ModelRegistry()
    front = reg.load_checkpoint("lenet5", str(tmp_path / "f"),
                                cascade_topk=3)
    big = reg.load_checkpoint("lenet5_big", str(tmp_path / "b"))
    plane = ModelControlPlane(
        reg, lambda m: BatchingEngine(m, buckets=[4], max_wait_ms=2),
        admission_factory=lambda name: AdmissionController(name=name))
    plane.deploy(front)
    plane.deploy(big)
    try:
        spec = CascadeSpec("lenet5", "lenet5_big", sample_period=1000,
                           min_sample=4, topk=3)
        router = CascadeRouter(plane, spec)
        x = np.random.RandomState(0).randint(
            0, 255, (32, 32, 1)).astype(np.float32)

        # uncalibrated: big answers, bit-identical to big-only serving
        tier, row = router.infer(x, timeout=120)
        assert tier == "big"
        direct = plane.infer("lenet5_big", x, timeout=120)
        np.testing.assert_array_equal(np.asarray(row),
                                      np.asarray(direct))

        # calibrate at 0.0: everything stops at the front tier, whose
        # engine scatters the fused top-K dict
        for _ in range(4):
            router.hist.record(0.0, True)
        router._recalibrate()
        assert router.threshold == 0.0
        tier, row = router.infer(x, timeout=120)
        assert tier == "front" and isinstance(row, dict)
        assert np.asarray(row["topk_class"]).shape == (3,)
        resp = ClassifyWorkload().respond(big, {"top_k": 3}, row)
        assert len(resp["top"]) == 3
        # front top-1 equals the front model served standalone
        fdirect = plane.infer("lenet5", x, timeout=120)
        assert ClassifyWorkload.top1(row)[0] == \
            ClassifyWorkload.top1(fdirect)[0]
    finally:
        plane.stop()
