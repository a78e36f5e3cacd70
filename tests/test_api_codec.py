"""API v1 codec contracts: deterministic, byte-stable JSON round trips for
every envelope type — property-based over arbitrary payloads (NaN/inf
deadlines, unicode job names, error envelopes) plus golden-pinned sample
encodings (a diff in the goldens is a wire-format break)."""
import json
import math
import os

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                       # deterministic example sweeps
    from _hyp_fallback import given, settings, strategies as st

from repro.api import codec
from repro.api.types import (AuthedRequest, ChooseRequest, ChooseResult,
                             CompactRequest, CompactResult,
                             ContributeRequest, ContributeResult,
                             HealthResult, JobInfo, LaneSnapshot,
                             ModelErrorsRequest, ModelErrorsResult,
                             PredictRequest, PredictResult, Response,
                             SearchRequest, SearchResult, StatsResult,
                             TrustStateRequest, TrustStateResult)

GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "goldens",
                           "api_v1.json")

#: job-name pool: plain ASCII, unicode, TSV-hostile characters
_JOBS = ("grep", "sørt-üser", "ページランク", "k\tmeans?", "job with spaces",
         '"quoted"')
_CONTRIBUTORS = ("unknown", "alice", "üser-42", "did:user:0x9f")
_SPECIALS = (math.nan, math.inf, -math.inf, 0.0, -0.0, 1e-300, 1e300)


def golden_samples():
    """The pinned wire-format corpus: one representative of every message
    type (regenerate DELIBERATELY with
    ``PYTHONPATH=src python tests/make_api_goldens.py``)."""
    return {
        "predict_request": PredictRequest(
            "grep", "m5.xlarge", ((4.0, 15.0, 0.02), (8.0, 15.0, 0.08))),
        "choose_request_nan_deadline": ChooseRequest(
            "sørt-üser", (12.5, 0.02), t_max=math.nan),
        "contribute_request": ContributeRequest(
            "grep", ("m5.xlarge", "c5.xlarge"),
            ((4.0, 15.0, 0.02), (8.0, 15.0, 0.08)), (120.5, 64.25),
            contributor_id="alice"),
        "model_errors_request": ModelErrorsRequest(
            "grep", "m5.xlarge", ((4.0, 15.0, 0.02),), (120.5,),
            track_models=("linreg", "gbm")),
        "search_request": SearchRequest("pagerank"),
        "choose_response": Response.success(ChooseResult(
            "c5.xlarge", 4, 174.8, 196.1, 0.0165, False)),
        "contribute_response": Response.success(ContributeResult(
            True, 0.031, 0.029, "accepted", "alice", 166, 1, "ab12" * 16)),
        "predict_response_inf_sigma": Response.success(PredictResult(
            (100.2, math.inf), "ogb", -3.8, math.nan)),
        "model_errors_response": Response.success(ModelErrorsResult(
            (("c3o", 0.003, 0.44), ("linreg", 0.31, 42.0)), "gbm")),
        "search_response": Response.success(SearchResult((JobInfo(
            "grep", "grep", 162, ("m5.xlarge",), ("ernest", "gbm"),
            (("alice", 4), ("unknown", 162))),))),
        "error_envelope": Response.failure(
            "unknown_job", "no published repo for job 'nope'"),
        # trust plane: token-wrapped requests, trust inspection, and the
        # typed refusal envelopes (unauthorized / quota_exceeded) plus the
        # lane-deadline timeout envelope
        "authed_choose_request": AuthedRequest(
            token="a3f1" * 8,
            request=ChooseRequest("grep", (15.0, 0.02), t_max=300.0)),
        "trust_state_request": TrustStateRequest("alice"),
        "trust_state_response": Response.success(TrustStateResult(
            "alice", True, False, 87.5,
            (("grep", 0.75, 3, 1), ("sort", 0.5, 0, 0)))),
        "trust_state_response_unmetered": Response.success(TrustStateResult(
            "üser-42", False, True, math.inf, ())),
        "unauthorized_envelope": Response.failure(
            "unauthorized", "unknown or revoked token"),
        "quota_envelope": Response.failure(
            "quota_exceeded", "rate quota exhausted for contributor "
            "'alice' (sustained 50/s, burst 100)"),
        "timeout_envelope": Response.failure(
            "timeout", "micro-batch dispatch exceeded its 0.25s deadline "
            "(3 request(s) affected)"),
        # store lifecycle: the operator-only compact op, its accepted
        # verdict, and a declined compaction (an ok envelope — a verdict,
        # not a transport failure)
        "compact_request": AuthedRequest(
            token="b2c4" * 8,
            request=CompactRequest("grep", max_rows_per_cell=2,
                                   support_floor=1, cell_rel_width=0.2,
                                   accuracy_budget=0.02, min_store_rows=32,
                                   seed=7)),
        "compact_response": Response.success(CompactResult(
            True, "compacted", "compacted 10000 -> 648 rows over 162 cells",
            10000, 648, 1, 162, 0.0096, 0.0095, 4, "cd34" * 16)),
        "compact_response_rejected": Response.success(CompactResult(
            False, "compaction_rejected",
            "store too small to compact: 42 rows < min_store_rows=64",
            42, 42, 1, 0, math.nan, math.nan, 3, "ef56" * 16)),
        # serving edge: GET /healthz and GET /stats payloads plus the
        # typed drain refusal every API op answers mid-shutdown
        "health_response": Response.success(HealthResult(
            "ok", "v1", ("grep", "sort"))),
        "health_response_draining": Response.success(HealthResult(
            "draining", "v1", ("grep",))),
        "stats_response": Response.success(StatsResult(
            1024, 3, 7, False, 12.25, 48.5, 96.125,
            (LaneSnapshot("grep", 238, 18, 13.2, 10.5, 30.25, 41.0),
             LaneSnapshot("grep@m5.xlarge#seed=7", 89, 17, 5.2, math.nan,
                          math.nan, math.nan)))),
        "shutting_down_envelope": Response.failure(
            "shutting_down", "edge is draining for shutdown; retry "
            "against another replica"),
        # cold-start transfer: answers borrowed from a donor job's models
        # carry transfer_source + a discounted transfer_confidence;
        # self-served envelopes omit both keys entirely (see the
        # omit-default samples above, which stay byte-identical)
        "predict_response_transfer": Response.success(PredictResult(
            (182.4, 96.75), "gbm", -2.1, 0.12,
            transfer_source="grep", transfer_confidence=0.56)),
        "choose_response_transfer": Response.success(ChooseResult(
            "m5.xlarge", 6, 210.0, 233.5, 0.021, True,
            transfer_source="sørt-üser", transfer_confidence=0.2)),
        # cloud market plane: placement-constrained requests, market-mode
        # answers stamped with the placement bought + the naive-vs-
        # adjusted cost breakdown, and the typed refusal for a placement
        # the book does not price (market-less envelopes above stay
        # byte-identical via the same omit-default mechanism)
        "choose_request_market": ChooseRequest(
            "grep", (15.0, 0.02), t_max=400.0,
            zones=("az-1a", "az-1b"), purchase_options=("spot",)),
        "choose_response_market": Response.success(ChooseResult(
            "c5.xlarge", 4, 174.8, 196.1, 0.0165, False,
            zone="az-1b", purchase_option="spot",
            expected_cost_usd=0.0184)),
        "placement_envelope": Response.failure(
            "bad_request", "unknown zone 'mars' (known zones: az-1a, "
            "az-1b, az-1c)"),
    }


# --------------------------------------------------------------------------
# golden-pinned wire format
# --------------------------------------------------------------------------

def test_golden_sample_encodings():
    with open(GOLDEN_PATH) as f:
        golden = json.load(f)
    samples = golden_samples()
    assert set(golden) == set(samples)
    for name, obj in samples.items():
        assert codec.encode(obj) == golden[name], \
            f"wire format drifted for {name}"
        back = codec.decode(golden[name])
        assert codec.encode(back) == golden[name]


def test_pre_epoch_jobinfo_payload_decodes_with_defaults():
    """JobInfo payloads minted before the store-lifecycle fields existed
    (no epoch/compactions/rows_contributed keys) still decode — the new
    fields default to the pre-epoch reading."""
    info = JobInfo("grep", "grep", 10, ("m5.xlarge",), ("gbm",),
                   (("alice", 10),))
    payload = json.loads(codec.encode(info))
    for k in ("epoch", "compactions", "rows_contributed"):
        payload.pop(k)
    back = codec.decode(json.dumps(payload))
    assert (back.epoch, back.compactions, back.rows_contributed) == (0, 0, 0)
    assert (back.job, back.rows) == ("grep", 10)


def test_pre_transfer_result_payloads_decode_with_defaults():
    """Result payloads minted before cold-start transfer existed (no
    transfer_source/transfer_confidence keys) decode to the self-served
    reading and re-encode byte-identically — the omit-default mechanism
    makes the legacy wire form THE canonical form for non-borrowed
    answers."""
    for legacy in (PredictResult((100.2,), "gbm", -3.8, 0.1),
                   ChooseResult("c5.xlarge", 4, 174.8, 196.1, 0.0165,
                                False)):
        text = codec.encode(legacy)
        assert "transfer" not in text
        back = codec.decode(text)
        assert back.transfer_source == ""
        assert back.transfer_confidence == 1.0
        assert codec.encode(back) == text


def test_pre_market_payloads_decode_with_defaults():
    """Choose payloads minted before the cloud market plane existed (no
    zones/purchase_options on requests, no zone/purchase_option/
    expected_cost_usd on results) decode to the static-price reading and
    re-encode byte-identically — the legacy wire form stays THE
    canonical form for market-less gateways."""
    req = ChooseRequest("grep", (15.0, 0.02), t_max=300.0)
    text = codec.encode(req)
    assert "zones" not in text and "purchase" not in text
    back = codec.decode(text)
    assert back.zones is None and back.purchase_options is None
    assert codec.encode(back) == text

    res = ChooseResult("c5.xlarge", 4, 174.8, 196.1, 0.0165, False)
    text = codec.encode(res)
    for key in ("zone", "purchase_option", "expected_cost_usd"):
        assert key not in text
    back = codec.decode(text)
    assert (back.zone, back.purchase_option, back.expected_cost_usd) \
        == ("", "", 0.0)
    assert codec.encode(back) == text
    # and the round trip back to the core dataclass carries the defaults
    choice = back.to_choice()
    assert (choice.zone, choice.purchase_option,
            choice.expected_cost_usd) == ("", "", 0.0)


def test_api_docs_are_current():
    """``docs/api_v1.md`` is generated from the live surface + goldens;
    any drift (new op, new error code, changed sample) fails here until
    ``PYTHONPATH=src python tests/make_api_docs.py`` is re-run."""
    import make_api_docs
    path = os.path.join(os.path.dirname(__file__), "..", "docs",
                        "api_v1.md")
    with open(path) as f:
        current = f.read()
    assert current == make_api_docs.render(), \
        "docs/api_v1.md is stale — regenerate with " \
        "PYTHONPATH=src python tests/make_api_docs.py"


def test_encoding_is_strict_json():
    """Every encoding parses under strict JSON rules (no NaN literals) —
    what makes the format consumable by non-Python HTTP peers."""
    for name, obj in golden_samples().items():
        parsed = json.loads(codec.encode(obj), parse_constant=lambda s: (
            _ for _ in ()).throw(AssertionError(f"{name}: non-strict {s}")))
        assert isinstance(parsed, dict)


# --------------------------------------------------------------------------
# property-based round trips
# --------------------------------------------------------------------------

def _eq(a, b):
    """Structural equality with NaN == NaN (dataclass __eq__ breaks on
    NaN fields, which are legal deadline/metric values)."""
    if isinstance(a, float) and isinstance(b, float):
        return (math.isnan(a) and math.isnan(b)) or a == b
    if type(a) is not type(b):
        return False
    if isinstance(a, tuple):
        return len(a) == len(b) and all(_eq(x, y) for x, y in zip(a, b))
    if hasattr(a, "__dataclass_fields__"):
        return all(_eq(getattr(a, f), getattr(b, f))
                   for f in a.__dataclass_fields__)
    return a == b


def _assert_roundtrip(msg):
    text = codec.encode(msg)
    back = codec.decode(text)
    assert _eq(back, msg), (msg, back)
    assert codec.encode(back) == text            # byte-stable


@settings(max_examples=40, deadline=None)
@given(job=st.sampled_from(_JOBS), c0=st.floats(-1e6, 1e6),
       special=st.sampled_from(_SPECIALS), use_special=st.booleans(),
       seed=st.integers(0, 2**31 - 1))
def test_choose_request_roundtrip(job, c0, special, use_special, seed):
    t_max = special if use_special else abs(c0) + 1.0
    _assert_roundtrip(ChooseRequest(job, (c0, special), t_max=t_max,
                                    seed=seed))


@settings(max_examples=30, deadline=None)
@given(job=st.sampled_from(_JOBS), contributor=st.sampled_from(_CONTRIBUTORS),
       n=st.integers(1, 5), v=st.floats(0.001, 1e9))
def test_contribute_request_roundtrip(job, contributor, n, v):
    _assert_roundtrip(ContributeRequest(
        job, ("m5.xlarge",) * n, tuple((float(i), v) for i in range(n)),
        tuple(v + i for i in range(n)), contributor_id=contributor))


@settings(max_examples=30, deadline=None)
@given(mape=st.sampled_from(_SPECIALS), rows=st.integers(0, 10**9),
       accepted=st.booleans(), job=st.sampled_from(_JOBS))
def test_result_envelope_roundtrip(mape, rows, accepted, job):
    _assert_roundtrip(Response.success(ContributeResult(
        accepted, mape, mape, f"verdict for {job}", "üser", rows, 3, "ff00")))
    _assert_roundtrip(Response.success(SearchResult((JobInfo(
        job, job, rows, ("m5.xlarge", "c5.xlarge"), ("gbm",),
        (("unknown", rows),)),))))


@settings(max_examples=30, deadline=None)
@given(code=st.sampled_from(("unknown_job", "bad_request", "internal")),
       detail=st.sampled_from(_JOBS))
def test_error_envelope_roundtrip(code, detail):
    msg = Response.failure(code, f"failed: {detail}")
    _assert_roundtrip(msg)
    back = codec.decode(codec.encode(msg))
    assert not back.ok and back.result is None
    assert back.error_code == code


@settings(max_examples=30, deadline=None)
@given(cid=st.sampled_from(_CONTRIBUTORS), rep=st.floats(0.0, 1.0),
       quota=st.sampled_from(_SPECIALS), banned=st.booleans(),
       job=st.sampled_from(_JOBS))
def test_trust_envelope_roundtrip(cid, rep, quota, banned, job):
    """Trust-plane envelopes round-trip byte-stably — including the
    nested request inside an AuthedRequest wrapper and the +inf
    quota_remaining of an unmetered gateway."""
    _assert_roundtrip(AuthedRequest(
        token="ff" * 16, request=TrustStateRequest(cid)))
    _assert_roundtrip(AuthedRequest(
        token="00" * 16,
        request=ChooseRequest(job, (1.0, rep), t_max=quota)))
    _assert_roundtrip(Response.success(TrustStateResult(
        cid, True, banned, quota, ((job, rep, 2, 1),))))


@settings(max_examples=30, deadline=None)
@given(job=st.sampled_from(_JOBS), draining=st.booleans(),
       p=st.sampled_from(_SPECIALS), requests=st.integers(0, 10**9),
       mean_batch=st.floats(0.0, 256.0))
def test_serving_envelope_roundtrip(job, draining, p, requests, mean_batch):
    """Serving-edge envelopes round-trip byte-stably — including NaN
    percentiles on never-dispatched lanes and the drain refusal."""
    _assert_roundtrip(Response.success(HealthResult(
        "draining" if draining else "ok", "v1", (job, "sort"))))
    _assert_roundtrip(Response.success(StatsResult(
        requests, 0, 3, draining, p, p, p,
        (LaneSnapshot(job, requests, 2, mean_batch, p, p, p),
         LaneSnapshot(f"{job}@m5.xlarge", 0, 0, 0.0, math.nan, math.nan,
                      math.nan)))))
    msg = Response.failure("shutting_down", f"draining; retry {job}")
    _assert_roundtrip(msg)
    back = codec.decode(codec.encode(msg))
    assert not back.ok and back.error_code == "shutting_down"


def test_stats_spans_roundtrip():
    """``/stats`` span rows (result and lane) round-trip byte-stably, and
    a snapshot without spans keeps the pre-span wire form."""
    spans = (("edge.decode", 12, 0.0031, 0.0031),
             ("engine.cv", 15, 31.5, 24.25))
    lane = LaneSnapshot("grep", 9, 8, 1.125, 5.5, 7.25, 9.0,
                        (("lane.wait", 9, 0.0012, 0.0012),))
    msg = Response.success(StatsResult(12, 0, 1, False, 6.0, 8.0, 9.5,
                                       (lane,), spans))
    _assert_roundtrip(msg)
    assert '"spans"' in codec.encode(msg)
    assert '"spans"' not in codec.encode(StatsResult(
        0, 0, 0, False, math.nan, math.nan, math.nan, ()))


def test_unencodable_value_raises():
    try:
        codec.encode(object())
    except TypeError:
        pass
    else:                                 # pragma: no cover
        raise AssertionError("expected TypeError for non-API payloads")
