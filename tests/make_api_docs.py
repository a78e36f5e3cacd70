"""Regenerate ``docs/api_v1.md`` from the live API surface + the golden
wire-format corpus.  The rendered page is CI-checked against this
generator (``test_api_docs_are_current``), so endpoint tables, error
codes, and payload samples can never drift from the code:

    PYTHONPATH=src python tests/make_api_docs.py
"""
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.dirname(__file__))

DOCS_PATH = os.path.join(os.path.dirname(__file__), "..", "docs",
                         "api_v1.md")

#: golden sample shown under each op's endpoint row (request, response)
_OP_SAMPLES = {
    "predict": ("predict_request", "predict_response_inf_sigma"),
    "choose": ("choose_request_nan_deadline", "choose_response"),
    "contribute": ("contribute_request", "contribute_response"),
    "model_errors": ("model_errors_request", "model_errors_response"),
    "search": ("search_request", "search_response"),
    "trust_state": ("trust_state_request", "trust_state_response"),
    "compact": ("compact_request", "compact_response"),
}

#: error envelopes worth a worked sample on the page
_ERROR_SAMPLES = ("error_envelope", "unauthorized_envelope",
                  "quota_envelope", "timeout_envelope",
                  "shutting_down_envelope")


#: the spans ``GET /stats`` can report, and where each is measured
_SPANS = (
    ("edge.read", "request body read"),
    ("edge.decode", "codec decode of the request body"),
    ("edge.encode", "codec encode of the response envelope"),
    ("edge.write", "response write and drain"),
    ("edge.request.<op>", "*counter*: app entry to response sent, per "
     "API operation"),
    ("edge.in_flight", "*counter*: each stretch with at least one API "
     "request in the edge (`/stats` and `/healthz` excluded)"),
    ("edge.idle", "each stretch with no API request in the edge"),
    ("gateway.admit", "admission and the lane lookup of a choose or "
     "single-row predict"),
    ("lane.wait", "*counter*: a request's wait on its lane, enqueue to "
     "the start of its tick"),
    ("lane.tick", "*counter*: one lane tick: pack, dispatch, fan-out"),
    ("lane.pack", "packing a tick's requests into one batch"),
    ("hub.service", "resolving the job and its configuration service in "
     "a choose tick"),
    ("service.select", "choosing from the scored grid and building the "
     "choices"),
    ("engine.dispatch", "one device program's enqueue: a predictor's, "
     "or a choose tick's whole machine grid"),
    ("engine.sync", "waiting for one device result on the host"),
    ("engine.cv", "a predictor's LOO-CV model selection"),
    ("engine.fit", "a predictor's final fit"),
    ("engine.lower", "*counter*: lowering an executable (jaxpr to MLIR), "
     "charged to the span it happened in"),
    ("engine.compile", "*counter*: compiling an executable or reading it "
     "from the compile cache"),
)


def _pretty(wire: str) -> str:
    return json.dumps(json.loads(wire), indent=2, sort_keys=True)


def render() -> str:
    """The full markdown page, deterministically, from the live surface."""
    from test_api_codec import GOLDEN_PATH, golden_samples

    from repro.api import codec
    from repro.api.types import API_VERSION
    from repro.serve.edge import OPS, STATUS_FOR_ERROR

    golden = {name: codec.encode(obj)
              for name, obj in golden_samples().items()}
    with open(GOLDEN_PATH) as f:
        pinned = json.load(f)
    assert golden == pinned, (
        "goldens are stale — run PYTHONPATH=src python "
        "tests/make_api_goldens.py first")

    out = []
    w = out.append
    w(f"# Hub Gateway API {API_VERSION} — HTTP surface")
    w("")
    w("<!-- GENERATED FILE — do not edit by hand.  Regenerate with")
    w("     PYTHONPATH=src python tests/make_api_docs.py -->")
    w("")
    w("The serving edge (`repro.serve.edge`) maps HTTP bodies through the")
    w("strict-JSON codec (`repro.api.codec`) into gateway operations.")
    w("Every request body and every response body is a codec-encoded")
    w("envelope: requests are the typed `*Request` dataclasses tagged with")
    w('`"__type__"`, responses are always a `Response` envelope'
      " (`status`")
    w('`"ok"` with a typed `result`, or `"error"` with a machine-readable')
    w("`error_code`).  Non-finite floats travel as tagged objects")
    w('(`{"__float__": "nan"}`), so the wire format is strict JSON.')
    w("")
    w("## Endpoints")
    w("")
    w("| Method | Path | Request envelope | Ok result |")
    w("|--------|------|------------------|-----------|")
    for op, req_t in OPS.items():
        resp_name = _OP_SAMPLES[op][1]
        result_t = json.loads(golden[resp_name])["result"]["__type__"]
        w(f"| POST | `/v1/{op}` | `{req_t.__name__}` | `{result_t}` |")
    w("| POST | `/v1` | any of the above (routes on `__type__`) | "
      "per request |")
    w("| GET | `/healthz` | — | `HealthResult` |")
    w("| GET | `/stats` | — | `StatsResult` |")
    w("")
    w("Any request MAY be wrapped in an `AuthedRequest` bearer-token")
    w("envelope; on auth-enabled gateways every operation MUST be.")
    w("Single-row `PredictRequest`s and `ChooseRequest`s coalesce on")
    w("per-(job, machine type) / per-job micro-batch lanes server-side;")
    w("batching is invisible in the response bytes.")
    w("")
    w("## Error codes")
    w("")
    w("Operational failures are ALWAYS typed envelopes — the HTTP status")
    w("is advisory for generic tooling, the envelope is the contract.")
    w("")
    w("| `error_code` | HTTP status |")
    w("|--------------|-------------|")
    for code, status in sorted(STATUS_FOR_ERROR.items()):
        w(f"| `{code}` | {status} |")
    w("")
    w("Protocol-level refusals (oversized header block: 431, chunked")
    w("transfer encoding: 400, body over the size cap: 413) answer the")
    w("same envelope shape with `error_code` `bad_request`.")
    w("")
    w("## Samples")
    w("")
    w("Request/response pairs below are the GOLDEN wire-format corpus")
    w("(`tests/goldens/api_v1.json`) — byte-pinned by the test suite,")
    w("pretty-printed here for reading.")
    for op in OPS:
        req_name, resp_name = _OP_SAMPLES[op]
        w("")
        w(f"### `POST /v1/{op}`")
        w("")
        w("Request:")
        w("")
        w("```json")
        w(_pretty(golden[req_name]))
        w("```")
        w("")
        w("Response:")
        w("")
        w("```json")
        w(_pretty(golden[resp_name]))
        w("```")
    w("")
    w("### `GET /healthz`")
    w("")
    w("```json")
    w(_pretty(golden["health_response"]))
    w("```")
    w("")
    w("During a drain the edge keeps answering health with status"
      ' `"draining"`:')
    w("")
    w("```json")
    w(_pretty(golden["health_response_draining"]))
    w("```")
    w("")
    w("### `GET /stats`")
    w("")
    w("```json")
    w(_pretty(golden["stats_response"]))
    w("```")
    w("")
    w("The result and each `LaneSnapshot` may also carry `spans`: one")
    w("`[name, count, total_s, self_s]` row per span name the program has")
    w("recorded since it started (`repro.core.trace`).  Self time is the")
    w("total less the spans recorded inside it.  A lane's snapshot holds")
    w("what its ticks recorded; the result holds everything else (the")
    w("edge, set-up fits, inline paths).  The key is absent while there")
    w("are none.  The spans, each also a `c3o.<name>` event in a")
    w("`jax.profiler` trace unless marked *counter*:")
    w("")
    w("| Span | Where |")
    w("|------|-------|")
    for name, where in _SPANS:
        w(f"| `{name}` | {where} |")
    w("")
    w("### Cold-start transfer")
    w("")
    w("On transfer-enabled gateways, `predict`/`choose` answers for a job")
    w("without enough history of its own are served from the nearest")
    w("donor job's fitted models and stamped with `transfer_source` and a")
    w("discounted `transfer_confidence`.  Self-served answers omit both")
    w("keys entirely, so pre-transfer payloads are byte-identical.")
    for name in ("predict_response_transfer", "choose_response_transfer"):
        w("")
        w("```json")
        w(_pretty(golden[name]))
        w("```")
    w("")
    w("### Spot markets & placement")
    w("")
    w("On market-enabled gateways (constructed with a")
    w("`repro.core.market.PriceBook`), `choose` scores a")
    w("(machine × zone × purchase-option × scale-out) grid on")
    w("interruption-adjusted expected cost.  Requests may constrain the")
    w("placement with `zones` / `purchase_options` (absent = any; an")
    w("empty tuple or an unknown name is a typed `bad_request`), and")
    w("answers stamp the placement bought plus the naive-vs-adjusted")
    w("cost breakdown: `cost_usd` stays the listed-price cost,")
    w("`expected_cost_usd` is what the choice is expected to really")
    w("cost once interruptions are priced in.  Market-less gateways")
    w("omit all of these keys, so pre-market payloads are")
    w("byte-identical.")
    for name in ("choose_request_market", "choose_response_market",
                 "placement_envelope"):
        w("")
        w("```json")
        w(_pretty(golden[name]))
        w("```")
    w("")
    w("### Error envelopes")
    for name in _ERROR_SAMPLES:
        w("")
        w("```json")
        w(_pretty(golden[name]))
        w("```")
    w("")
    return "\n".join(out)


def main() -> None:
    text = render()
    os.makedirs(os.path.dirname(DOCS_PATH), exist_ok=True)
    with open(DOCS_PATH, "w") as f:
        f.write(text)
    print(f"wrote {os.path.normpath(DOCS_PATH)} ({len(text)} bytes)")


if __name__ == "__main__":
    main()
