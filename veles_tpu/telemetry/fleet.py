"""Fleet /metrics aggregation: scrape-and-merge over N endpoints.

The ROADMAP-item-1 topology is N engine replicas behind a router; its
observability substrate is ONE fleet-wide /metrics view — "what is
p99 TTFT across the fleet", not per replica. This module scrapes the
Prometheus exposition every veles_tpu HTTP surface renders (the
shared :func:`~veles_tpu.telemetry.counters.metrics_text` path on
web_status, RESTfulAPI and GenerationAPI) and merges:

- **counters** are SUMMED (each is a per-process monotonic total);
- **histogram buckets** are SUMMED per ``le`` bound, ``_sum`` and
  ``_count`` with them — fixed buckets make this lossless, which is
  exactly why the registry uses fixed bounds instead of per-process
  quantile sketches — and the fleet p50/p90/p99 are RECOMPUTED from
  the merged buckets (never averaged from per-endpoint quantiles,
  which is statistically meaningless);
- **gauges** are SUMMED (slots busy, queue depth, pages in use — the
  fleet totals an admission/spill/drain router decides on); the
  per-endpoint quantile gauges the endpoints derive from their own
  buckets are DROPPED (they are recomputed fleet-wide);
- per-endpoint **up/down status** rides along as
  ``veles_fleet_endpoint_up{endpoint="..."}`` rows, so a dead
  replica is visible in the very page that hides its counters.

CLI: ``veles-tpu metrics aggregate URL [URL ...]`` prints the merged
exposition; ``--json`` prints the structured form. Operator guide:
docs/observability.md "Request-plane SLOs".
"""

from __future__ import annotations

import json
import re
from typing import Dict, List, Optional, Sequence, Tuple

from .counters import (METRICS_CONTENT_TYPE,          # noqa: F401
                       QUANTILE_GAUGES, describe_counter,
                       describe_histogram, gauge_text,
                       histogram_quantile, inc)

#: quantile-gauge suffixes the endpoints derive locally — dropped on
#: merge and recomputed from the merged buckets
_QUANTILE_SUFFIXES = tuple("_" + label for _q, label in QUANTILE_GAUGES)

#: one exposition sample line: ``name{labels} value`` or ``name value``
_SAMPLE_RE = re.compile(
    r"^([A-Za-z_:][A-Za-z0-9_:]*)(\{([^}]*)\})?\s+(\S+)$")

_LE_RE = re.compile(r'le="([^"]+)"')


def parse_metrics_text(text: str) -> Dict[str, Dict]:
    """Prometheus exposition text → ``{"counters": {name: value},
    "gauges": {...}, "histograms": {name: {"buckets": {le: cum},
    "sum": s, "count": n}}}``. ``# TYPE`` lines drive classification;
    untyped samples land in gauges (safe: summing an unknown series
    is no worse than dropping it, and the names stay visible).
    Labeled series other than histogram ``le`` buckets are skipped —
    the veles surfaces emit none, and guessing how to merge foreign
    labels would corrupt the page."""
    types: Dict[str, str] = {}
    counters: Dict[str, float] = {}
    gauges: Dict[str, float] = {}
    hists: Dict[str, Dict] = {}

    def hist(base: str) -> Dict:
        return hists.setdefault(
            base, {"buckets": {}, "sum": 0.0, "count": 0.0})

    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        if line.startswith("#"):
            parts = line.split(None, 3)
            if len(parts) >= 4 and parts[1] == "TYPE":
                types[parts[2]] = parts[3].strip()
            continue
        m = _SAMPLE_RE.match(line)
        if not m:
            continue
        name, labels, raw = m.group(1), m.group(3), m.group(4)
        try:
            value = float(raw)
        except ValueError:
            continue
        if name.endswith("_bucket") \
                and types.get(name[:-7]) == "histogram":
            le = _LE_RE.search(labels or "")
            if le:
                hist(name[:-7])["buckets"][le.group(1)] = value
            continue
        if name.endswith("_sum") and types.get(name[:-4]) == "histogram":
            hist(name[:-4])["sum"] = value
            continue
        if name.endswith("_count") \
                and types.get(name[:-6]) == "histogram":
            hist(name[:-6])["count"] = value
            continue
        if labels:
            continue
        if types.get(name) == "counter":
            counters[name] = value
        else:
            gauges[name] = value
    return {"counters": counters, "gauges": gauges,
            "histograms": hists}


def _le_value(le: str) -> float:
    return float("inf") if le == "+Inf" else float(le)


def _cum_at(buckets: Dict[str, float], bound: float) -> float:
    """Cumulative count of a histogram at ``bound`` — the largest
    recorded cumulative count at a bound <= ``bound`` (the step
    function a cumulative histogram IS), so endpoints with different
    bucket grids still merge exactly at their common bounds."""
    best = 0.0
    for le, cum in buckets.items():
        if _le_value(le) <= bound:
            best = max(best, cum)
    return best


def merge(parsed: Sequence[Dict[str, Dict]]) -> Dict[str, Dict]:
    """Merge N :func:`parse_metrics_text` results into one fleet
    view: counters and gauges summed, histogram buckets summed per
    bound (union of bounds, each endpoint evaluated as the step
    function its cumulative buckets define), sums/counts summed.
    Per-endpoint quantile gauges are dropped — :func:`quantiles`
    recomputes them from the merged buckets."""
    out: Dict[str, Dict] = {"counters": {}, "gauges": {},
                            "histograms": {}}
    for p in parsed:
        for name, val in p.get("counters", {}).items():
            out["counters"][name] = out["counters"].get(name, 0.0) + val
        for name, val in p.get("gauges", {}).items():
            if name == "veles_serving_tp":
                # mesh-slice width, NOT additive load: a tp=4 replica
                # is ONE endpoint spanning 4 chips — fold the widths
                # into the fleet chip total (solo engines export
                # tp=1) instead of letting the generic sum read as
                # "4 of something" on one replica's row
                out["gauges"]["veles_fleet_chips"] = (
                    out["gauges"].get("veles_fleet_chips", 0.0)
                    + max(1.0, val))
                continue
            out["gauges"][name] = out["gauges"].get(name, 0.0) + val
        for name, h in p.get("histograms", {}).items():
            tgt = out["histograms"].setdefault(
                name, {"buckets": {}, "sum": 0.0, "count": 0.0})
            bounds = {le for le in h["buckets"]} \
                | set(tgt["buckets"])
            merged = {}
            for le in bounds:
                merged[le] = (_cum_at(tgt["buckets"], _le_value(le))
                              + _cum_at(h["buckets"], _le_value(le)))
            tgt["buckets"] = merged
            tgt["sum"] += h["sum"]
            tgt["count"] += h["count"]
    # drop the per-endpoint quantile gauges in one pass over the
    # FINAL histogram name set (they are recomputed fleet-wide)
    for name in list(out["gauges"]):
        if any(name == h + s for h in out["histograms"]
               for s in _QUANTILE_SUFFIXES):
            del out["gauges"][name]
    return out


def hist_to_snapshot(hist: Dict) -> Dict:
    """A merged CUMULATIVE-bucket histogram (the exposition form) →
    the registry-snapshot form (``{"bounds", "counts"
    (non-cumulative, + overflow), "sum", "count"}``) —  exactly what
    :meth:`~veles_tpu.telemetry.timeseries.SeriesStore.ingest`
    stores, so a remote scrape and a local registry sample derive
    windowed quantiles through the same arithmetic."""
    items = sorted(((le, cum) for le, cum in hist["buckets"].items()
                    if le != "+Inf"),
                   key=lambda kv: _le_value(kv[0]))
    bounds = [_le_value(le) for le, _ in items]
    counts: List[float] = []
    prev = 0.0
    for _le, cum in items:
        counts.append(max(0.0, cum - prev))
        prev = max(prev, cum)
    counts.append(max(0.0, float(hist["count"]) - prev))  # +Inf bucket
    return {"bounds": bounds, "counts": counts,
            "sum": float(hist.get("sum", 0.0)),
            "count": float(hist.get("count", 0.0))}


def quantiles(hist: Dict, qs=(0.5, 0.9, 0.99)) -> Dict[float, Optional[float]]:
    """Recompute quantiles from a merged histogram's CUMULATIVE
    buckets (the exposition form) via the shared
    :func:`histogram_quantile` arithmetic."""
    snap = hist_to_snapshot(hist)
    return {q: histogram_quantile(snap["bounds"], snap["counts"], q)
            for q in qs}


def ingest_aggregate(store, agg: Dict, ts: Optional[float] = None
                     ) -> None:
    """Feed one :func:`aggregate` result into a client-side
    :class:`~veles_tpu.telemetry.timeseries.SeriesStore` (built with
    ``count_samples=False`` — a watching CLI must not move the
    watched fleet's, or its own process's, watch counters). The
    endpoint up/down status rides along as fleet gauges so the watch
    loop can display roster health from the same ring."""
    merged = agg["merged"]
    hists = {name: hist_to_snapshot(h)
             for name, h in merged["histograms"].items()}
    gauges = dict(merged["gauges"])
    gauges["veles_fleet_endpoints"] = len(agg["endpoints"])
    gauges["veles_fleet_endpoints_up"] = sum(
        1 for ep in agg["endpoints"] if ep["up"])
    store.ingest(merged["counters"], hists, gauges, ts=ts)


def interval_report(store, window: Optional[float] = None) -> Dict:
    """One watch-interval summary from a client-side store: request/
    token rates and WINDOWED latency quantiles (bucket deltas between
    the window's endpoint samples — the cumulative ``_p99`` gauges on
    the scrape page would bury a brownout under the whole run's
    history), plus the fleet occupancy gauges of the newest sample.
    Values are None until two samples exist."""
    def _r(v, nd=3):
        return None if v is None else round(v, nd)
    return {
        "up": store.gauge("veles_fleet_endpoints_up"),
        "endpoints": store.gauge("veles_fleet_endpoints"),
        "qps": _r(store.rate("veles_serving_retired_total", window)),
        "tok_s": _r(store.rate("veles_serving_tokens_total", window)),
        "shed_s": _r(store.rate("veles_shed_requests_total", window)),
        "ttft_p50": _r(store.quantile(
            "veles_serving_ttft_seconds", 0.5, window), 4),
        "ttft_p99": _r(store.quantile(
            "veles_serving_ttft_seconds", 0.99, window), 4),
        "tpot_p50": _r(store.quantile(
            "veles_serving_tpot_seconds", 0.5, window), 4),
        "tpot_p99": _r(store.quantile(
            "veles_serving_tpot_seconds", 0.99, window), 4),
        "e2e_p99": _r(store.quantile(
            "veles_serving_e2e_seconds", 0.99, window), 4),
        "slots_busy": store.gauge("veles_serving_slots_busy"),
        "slots": store.gauge("veles_serving_slots"),
        "queue_depth": store.gauge("veles_serving_queue_depth"),
        "brownout": store.gauge("veles_qos_brownout_level"),
        "admit_rate": store.gauge("veles_qos_admit_rate"),
    }


def format_interval(rep: Dict) -> str:
    """One terminal line per watch interval (``veles-tpu metrics
    aggregate --watch``)."""
    def fmt(v, unit=""):
        return "-" if v is None else ("%g%s" % (v, unit))
    parts = ["up %s/%s" % (fmt(rep["up"]), fmt(rep["endpoints"])),
             "qps %s" % fmt(rep["qps"]),
             "tok/s %s" % fmt(rep["tok_s"]),
             "ttft p50/p99 %s/%s" % (fmt(rep["ttft_p50"], "s"),
                                     fmt(rep["ttft_p99"], "s")),
             "e2e p99 %s" % fmt(rep["e2e_p99"], "s"),
             "busy %s/%s" % (fmt(rep["slots_busy"]),
                             fmt(rep["slots"])),
             "queue %s" % fmt(rep["queue_depth"])]
    if rep.get("shed_s"):
        parts.append("shed/s %s" % fmt(rep["shed_s"]))
    if rep.get("brownout"):
        parts.append("brownout L%s" % fmt(rep["brownout"]))
    return "  ".join(parts)


def read_endpoints(path: str) -> List[str]:
    """Replica roster from a file — the ONE roster format fleet
    scraping (``veles-tpu metrics aggregate --endpoints-file``) and
    routing (``veles-tpu route --endpoints-file``) share. Two forms:

    - plain text: one endpoint per line, ``#`` comments and blank
      lines ignored;
    - JSON: a bare list of URLs, or an object with an ``"endpoints"``
      list whose items are URLs or ``{"url": ...}`` dicts — exactly
      what the router's ``GET /roster`` page is, so discovery output
      saved to disk feeds both consumers unchanged.

    Raises ValueError on malformed JSON/entries; an empty roster is
    the caller's error to report."""
    with open(path) as fin:
        text = fin.read()
    stripped = text.lstrip()
    if stripped.startswith("{") or stripped.startswith("["):
        doc = json.loads(text)
        items = doc.get("endpoints", []) if isinstance(doc, dict) \
            else doc
        out: List[str] = []
        for item in items:
            if isinstance(item, dict):
                url = item.get("url")
                if not isinstance(url, str) or not url:
                    raise ValueError(
                        "roster entry %r carries no \"url\"" % (item,))
                out.append(url)
            elif isinstance(item, str):
                out.append(item)
            else:
                raise ValueError("roster entry %r is neither a URL "
                                 "string nor a dict" % (item,))
        return out
    return [line for raw in text.splitlines()
            for line in [raw.split("#", 1)[0].strip()] if line]


def scrape(url: str, timeout: float = 5.0
           ) -> Tuple[Optional[str], Optional[str]]:
    """(body, error) for one /metrics endpoint — exactly one of the
    two is None. Bare host:port inputs get ``http://`` and
    ``/metrics`` filled in."""
    import urllib.error
    import urllib.request
    if "://" not in url:
        url = "http://" + url
    if not url.rstrip("/").endswith("/metrics"):
        url = url.rstrip("/") + "/metrics"
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.read().decode("utf-8", "replace"), None
    except Exception as e:      # noqa: BLE001 — a down replica is data
        return None, "%s: %s" % (type(e).__name__, e)


def aggregate(urls: Sequence[str], timeout: float = 5.0) -> Dict:
    """Scrape every endpoint and merge the live ones. Returns
    ``{"endpoints": [{"url", "up", "error"}...], "merged": {...}}`` —
    a down endpoint contributes its up=0 row and nothing else."""
    statuses = []
    parsed = []
    for url in urls:
        body, error = scrape(url, timeout=timeout)
        statuses.append({"url": url, "up": body is not None,
                         "error": error})
        if body is not None:
            parsed.append(parse_metrics_text(body))
    return {"endpoints": statuses, "merged": merge(parsed)}


def render(agg: Dict) -> str:
    """One fleet-wide exposition page from an :func:`aggregate`
    result: endpoint status rows, summed counters, merged histograms
    with RECOMPUTED p50/p90/p99 gauges, summed gauges."""
    lines = [
        "# HELP veles_fleet_endpoint_up 1 = endpoint scraped "
        "successfully, 0 = down",
        "# TYPE veles_fleet_endpoint_up gauge",
    ]
    for ep in agg["endpoints"]:
        lines.append('veles_fleet_endpoint_up{endpoint="%s"} %d'
                     % (ep["url"], 1 if ep["up"] else 0))
    text = "\n".join(lines) + "\n"
    text += gauge_text("veles_fleet_endpoints", len(agg["endpoints"]),
                       "Endpoints this aggregation covers")
    text += gauge_text("veles_fleet_endpoints_up",
                       sum(1 for ep in agg["endpoints"] if ep["up"]),
                       "Endpoints that answered the scrape")
    merged = agg["merged"]
    for name in sorted(merged["counters"]):
        val = merged["counters"][name]
        text += "# HELP %s %s\n# TYPE %s counter\n%s %s\n" % (
            name, describe_counter(name), name, name,
            int(val) if float(val).is_integer() else val)
    for name in sorted(merged["histograms"]):
        h = merged["histograms"][name]
        text += "# HELP %s %s\n# TYPE %s histogram\n" % (
            name, describe_histogram(name), name)
        for le, cum in sorted(h["buckets"].items(),
                              key=lambda kv: _le_value(kv[0])):
            text += '%s_bucket{le="%s"} %d\n' % (name, le, cum)
        if "+Inf" not in h["buckets"]:
            text += '%s_bucket{le="+Inf"} %d\n' % (name, h["count"])
        text += "%s_sum %s\n%s_count %d\n" % (
            name, round(float(h["sum"]), 9), name, h["count"])
        if h["count"]:
            qs = quantiles(h)
            for q, label in QUANTILE_GAUGES:
                if qs.get(q) is not None:
                    text += gauge_text(
                        "%s_%s" % (name, label), round(qs[q], 9),
                        "Fleet-recomputed %s of %s" % (label, name))
    for name in sorted(merged["gauges"]):
        val = merged["gauges"][name]
        text += gauge_text(name, val)
    return text


# -- fleet-wide distributed tracing (span pulls + timeline assembly) ----------
#
# The trace twin of the /metrics aggregation above: every request-
# plane HTTP surface serves its bounded span ring at GET
# /trace/spans?since=CURSOR (telemetry/spans.pull_payload — JSONL, a
# header line + one line per span), and `veles-tpu trace fleet` pulls
# the router's + every replica's rings, estimates per-process clock
# offsets by BRACKETING alignment — each router route.attempt span
# must contain, in true time, the replica `request` span carrying the
# same (trace_id, attempt) — and merges everything into ONE Chrome
# trace with one lane per process. The offset technique is
# devtime.attribute_spans' window alignment reapplied host-to-host;
# like there, it is an approximation: the estimate is only as tight
# as the attempt-minus-request slack (network + HTTP framing time),
# stated in docs/observability.md "Fleet tracing".

def _base_url(url: str) -> str:
    url = str(url).strip()
    if "://" not in url:
        url = "http://" + url
    url = url.rstrip("/")
    if url.endswith("/metrics"):
        url = url[:-len("/metrics")]
    return url


def scrape_spans(url: str, since: int = 0, timeout: float = 5.0
                 ) -> Tuple[Optional[str], Optional[str]]:
    """(body, error) for one ``/trace/spans`` endpoint — exactly one
    of the two is None (the :func:`scrape` contract, for span
    rings)."""
    import urllib.request
    full = "%s/trace/spans?since=%d" % (_base_url(url), int(since))
    try:
        with urllib.request.urlopen(full, timeout=timeout) as r:
            return r.read().decode("utf-8", "replace"), None
    except Exception as e:      # noqa: BLE001 — a down replica is data
        return None, "%s: %s" % (type(e).__name__, e)


def parse_span_payload(text: str) -> Dict:
    """One ``/trace/spans`` JSONL body → ``{"header": {...} | None,
    "spans": [...], "bad": n}``. Torn lines — a response truncated
    mid-record by a dying replica or a cut connection — are skipped
    with ONE counted warning (the ``spans.read_jsonl`` salvage rule):
    the complete prefix still assembles."""
    import logging
    header: Optional[Dict] = None
    spans: List[Dict] = []
    bad = 0
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            bad += 1
            continue
        if not isinstance(rec, dict):
            bad += 1
            continue
        if rec.get("kind") == "spans.header":
            if header is None:
                header = rec
            continue
        # sanitize HERE, the one remote-data entry point: every
        # consumer downstream (grouping sort, bracketing pairs, lane
        # conversion) does float arithmetic on ts/dur, and a corrupt
        # record from a damaged ring must quarantine like a torn
        # line, never crash the assembler with a TypeError
        ts = rec.get("ts")
        dur = rec.get("dur", 0.0)
        if "name" not in rec \
                or not isinstance(ts, (int, float)) \
                or isinstance(ts, bool):
            bad += 1
            continue
        if not isinstance(dur, (int, float)) or isinstance(dur, bool):
            rec = dict(rec, dur=0.0)
        try:
            tid = int(rec.get("tid", 0))
        except (TypeError, ValueError):
            tid = 0
        if rec.get("tid", 0) != tid:
            rec = dict(rec, tid=tid)
        spans.append(rec)
    if bad:
        logging.getLogger("veles_tpu.telemetry").warning(
            "skipped %d torn/malformed line(s) in a /trace/spans "
            "payload (truncated mid-record; the complete prefix "
            "still assembles)", bad)
    return {"header": header, "spans": spans, "bad": bad}


def _group_processes(payloads: Sequence[Dict]) -> Dict:
    """Payloads (``{"url", "header", "spans"}``) → per-PROCESS span
    sets keyed by the header's ``instance`` token (falling back to
    the bare pid for payloads from builds without one — pids are
    per-host, so two hosts CAN hold distinct processes with one
    pid): ``{key: {"pid", "names", "spans"}}``, deduplicated within
    a process by the records' pull cursor — an in-process fleet
    (N replicas + router sharing one python process, the tests'
    topology) pulls the SAME process-global ring through every
    endpoint, and triple-counting it would triple every lane."""
    procs: Dict = {}
    for payload in payloads:
        header = payload.get("header")
        if header is None:
            # a payload whose header line was torn away still merges
            # — keyed by its URL so two headerless SOURCES never
            # coalesce into one lane (their seq counters both start
            # at 1 and would cross-dedup each other's spans)
            header = {}
            key = "headerless:%s" % payload.get("url")
            pid = 0
        else:
            try:
                pid = int(header.get("pid", 0) or 0)
            except (TypeError, ValueError):
                # a damaged header quarantines like a torn record —
                # it must not crash the merge of healthy endpoints
                pid = 0
            key = header.get("instance") or pid
        entry = procs.setdefault(key, {"pid": pid, "names": [],
                                       "seen": {}, "spans": []})
        name = str(header.get("name") or payload.get("url") or "")
        if name and name not in entry["names"]:
            entry["names"].append(name)
        for rec in payload.get("spans", ()):
            dedup = (rec.get("seq"), rec.get("sid"), rec.get("ts"))
            if dedup in entry["seen"]:
                continue
            entry["seen"][dedup] = True
            entry["spans"].append(rec)
    for entry in procs.values():
        entry.pop("seen")
        entry["spans"].sort(key=lambda r: float(r.get("ts", 0.0)))
    return procs


def _bracket_pairs(attempts: Sequence[Dict], requests: Sequence[Dict]
                   ) -> List[Tuple[float, float]]:
    """Offset-bound intervals ``[lo, hi]`` (replica_clock −
    router_clock, seconds) from (route.attempt, request) span pairs
    sharing (trace_id, attempt): in true time the attempt brackets
    the replica's request span, so ``R_end − A_end ≤ offset ≤
    R_start − A_start``."""
    by_key = {}
    for a in attempts:
        key = (a.get("trace_id"), a.get("attempt"))
        if None not in key:
            by_key.setdefault(key, a)
    out: List[Tuple[float, float]] = []
    for r in requests:
        a = by_key.get((r.get("trace_id"), r.get("attempt")))
        if a is None:
            continue
        a0, a1 = float(a["ts"]), float(a["ts"]) + float(
            a.get("dur", 0.0))
        r0, r1 = float(r["ts"]), float(r["ts"]) + float(
            r.get("dur", 0.0))
        lo, hi = r1 - a1, r0 - a0
        if lo <= hi:
            out.append((lo, hi))
    return out


def estimate_offsets(procs: Dict) -> Dict:
    """Per-process clock offsets onto the ROUTER's clock, keyed like
    ``procs``: ``{key: {"pid", "offset": seconds, "pairs": n,
    "bound": slack}}``. The reference process is the one emitting
    ``route.attempt`` spans (offset 0 by definition); every other
    process's offset is the midpoint of the intersected bracketing
    intervals (median of midpoints when noise empties the
    intersection), ``bound`` the final interval's width — the stated
    uncertainty of the estimate. A process with no bracketing pair
    keeps offset 0 with ``pairs: 0`` (assembled on its own clock,
    flagged in the CLI summary)."""
    ref_key = None
    for key, entry in sorted(procs.items(), key=lambda kv: str(kv[0])):
        if any(r.get("name") == "route.attempt"
               for r in entry["spans"]):
            ref_key = key
            break
    if ref_key is None and procs:
        ref_key = sorted(procs, key=str)[0]
    out: Dict = {}
    attempts = [r for r in procs.get(ref_key, {}).get("spans", ())
                if r.get("name") == "route.attempt"] \
        if ref_key is not None else []
    for key, entry in procs.items():
        pid = entry.get("pid", 0)
        if key == ref_key:
            out[key] = {"pid": pid, "offset": 0.0, "pairs": 0,
                        "bound": 0.0, "reference": True}
            continue
        requests = [r for r in entry["spans"]
                    if r.get("name") == "request"]
        pairs = _bracket_pairs(attempts, requests)
        if not pairs:
            out[key] = {"pid": pid, "offset": 0.0, "pairs": 0,
                        "bound": None}
            continue
        lo = max(p[0] for p in pairs)
        hi = min(p[1] for p in pairs)
        if lo <= hi:
            offset, bound = (lo + hi) / 2.0, hi - lo
        else:
            # noisy pairs emptied the intersection: fall back to the
            # median of per-pair midpoints
            mids = sorted((a + b) / 2.0 for a, b in pairs)
            offset = mids[len(mids) // 2]
            bound = max(b - a for a, b in pairs)
        out[key] = {"pid": pid, "offset": offset,
                    "pairs": len(pairs), "bound": bound}
    return out


def assemble_fleet_trace(payloads: Sequence[Dict],
                         request: Optional[str] = None
                         ) -> Tuple[Dict, Dict]:
    """Merge N parsed ``/trace/spans`` payloads into ONE Chrome trace
    document: spans deduplicated per process, each process's clock
    shifted onto the router's by the bracketing estimate, one
    Perfetto lane per process. ``request`` keeps only one request's
    story — every span whose ``trace_id`` matches (resolving a
    request_id to its trace first), so the timeline reads: queue at
    the router, attempt 1, replica death, backoff, attempt 2 with
    resume, first token, terminal. Returns ``(trace document,
    summary)``; raises ValueError when nothing survives (an empty
    Perfetto page helps nobody). Counted
    ``veles_trace_fleet_merges_total``."""
    from . import chrome_trace
    procs = _group_processes(payloads)
    offsets = estimate_offsets(procs)
    if request is not None:
        from .spans import matches_request
        tids = {str(r.get("trace_id"))
                for entry in procs.values() for r in entry["spans"]
                if matches_request(r, request)
                and r.get("trace_id") is not None}
        if not tids:
            raise ValueError(
                "no span tagged request_id/trace_id %s in any pulled "
                "ring" % request)
    processes = []
    total = 0
    for key in sorted(procs,
                      key=lambda p: (not offsets[p].get("reference"),
                                     str(p))):
        entry = procs[key]
        off = offsets[key]["offset"]
        recs = []
        for rec in entry["spans"]:
            if request is not None \
                    and str(rec.get("trace_id")) not in tids \
                    and str(rec.get("request_id")) != str(request):
                continue
            out = dict(rec, ts=float(rec["ts"]) - off)
            if off:
                out["clock_offset_s"] = round(off, 6)
            recs.append(out)
        if not recs:
            # a process the --request filter emptied renders no lane
            # — and must not inflate the summary's lane count either
            continue
        total += len(recs)
        processes.append({
            "name": "%s (pid %d)" % ("+".join(entry["names"])
                                     or "process", entry["pid"]),
            "records": recs,
        })
    if not total:
        raise ValueError("no spans to assemble (empty rings%s)"
                         % (", or nothing tagged %s" % request
                            if request else ""))
    doc = {"traceEvents": chrome_trace.fleet_trace_events(processes),
           "displayTimeUnit": "ms"}
    errors = chrome_trace.validate(doc)
    if errors:        # assembler bug, not user input — fail loudly
        raise ValueError("invalid fleet trace produced: %s"
                         % errors[:3])
    inc("veles_trace_fleet_merges_total")
    summary = {
        "processes": len(processes),
        "spans": total,
        "offsets": {key: dict(offsets[key],
                              offset=round(offsets[key]["offset"], 6))
                    for key in offsets},
    }
    if request is not None:
        summary["trace_ids"] = sorted(tids)
    return doc, summary


def trace_fleet(urls: Sequence[str], request: Optional[str] = None,
                since: int = 0, timeout: float = 5.0
                ) -> Tuple[Dict, Dict]:
    """Pull every endpoint's span ring and assemble the fleet trace
    (``veles-tpu trace fleet`` driver). Down endpoints degrade to
    up=0 rows in the summary — the merge runs over whoever answered;
    raises ValueError when NOBODY did."""
    payloads = []
    statuses = []
    for url in urls:
        body, error = scrape_spans(url, since=since, timeout=timeout)
        statuses.append({"url": url, "up": body is not None,
                         "error": error})
        if body is None:
            continue
        parsed = parse_span_payload(body)
        parsed["url"] = url
        payloads.append(parsed)
    if not payloads:
        raise ValueError(
            "no /trace/spans endpoint answered (%s)"
            % "; ".join("%s: %s" % (s["url"], s["error"])
                        for s in statuses))
    doc, summary = assemble_fleet_trace(payloads, request=request)
    summary["endpoints"] = statuses
    return doc, summary


def main(argv) -> int:
    """``veles-tpu metrics aggregate URL [URL ...]`` driver (wired in
    veles_tpu/__main__.py). Exit 0 while at least one endpoint
    answered; 2 when the whole fleet is down (the merged page would
    be empty — an alert, not a report)."""
    import argparse
    parser = argparse.ArgumentParser(
        prog="veles_tpu metrics",
        description="fleet /metrics tools (telemetry/fleet.py)")
    sub = parser.add_subparsers(dest="cmd", required=True)
    ag = sub.add_parser(
        "aggregate",
        help="scrape N /metrics endpoints, print the merged "
             "exposition (counters/buckets summed, quantiles "
             "recomputed, per-endpoint up/down rows)")
    ag.add_argument("urls", nargs="*", metavar="URL",
                    help="endpoint (http://host:port[/metrics]; bare "
                         "host:port accepted)")
    ag.add_argument("--endpoints-file", default=None, metavar="FILE",
                    help="replica roster file shared with the fleet "
                         "router: one endpoint per line (# comments), "
                         "or JSON — a bare URL list or the router's "
                         "GET /roster output saved to disk")
    ag.add_argument("--timeout", type=float, default=5.0,
                    help="per-endpoint scrape timeout, seconds")
    ag.add_argument("--json", action="store_true",
                    help="print the structured aggregation instead "
                         "of exposition text")
    ag.add_argument("--watch", type=float, default=None, metavar="SEC",
                    help="interval mode: re-scrape every SEC seconds "
                         "and print one summary line per interval "
                         "(windowed rates/quantiles from sample "
                         "deltas via the watchtower SeriesStore) "
                         "instead of one exposition page")
    ag.add_argument("--iterations", type=int, default=0, metavar="N",
                    help="with --watch: stop after N intervals "
                         "(0 = run until interrupted)")
    args = parser.parse_args(argv)
    urls = list(args.urls)
    if args.endpoints_file:
        try:
            urls += read_endpoints(args.endpoints_file)
        except (OSError, ValueError) as e:
            parser.error("bad --endpoints-file: %s" % e)
    if not urls:
        parser.error("no endpoints (positional URLs and/or "
                     "--endpoints-file)")
    if args.watch is not None:
        if args.watch <= 0:
            parser.error("--watch period must be > 0")
        return watch_aggregate(urls, period=args.watch,
                               iterations=args.iterations,
                               timeout=args.timeout,
                               as_json=args.json)
    agg = aggregate(urls, timeout=args.timeout)
    if args.json:
        print(json.dumps(agg, indent=2, sort_keys=True))
    else:
        print(render(agg), end="")
    return 0 if any(ep["up"] for ep in agg["endpoints"]) else 2


def watch_aggregate(urls: Sequence[str], period: float,
                    iterations: int = 0, timeout: float = 5.0,
                    as_json: bool = False, out=print) -> int:
    """``veles-tpu metrics aggregate --watch SEC`` driver: a scrape +
    merge + :func:`ingest_aggregate` loop over a client-side
    :class:`~veles_tpu.telemetry.timeseries.SeriesStore`
    (``count_samples=False``), one summary line per interval —
    windowed rates and quantiles computed EXACTLY like a replica's
    own watchtower computes them. Exit 0 while the last interval saw
    at least one endpoint up; 2 otherwise."""
    import time as _time
    from .timeseries import SeriesStore
    store = SeriesStore(period=period,
                        retention=max(600.0, period * 600),
                        count_samples=False)
    n = 0
    last_up = 0
    try:
        while True:
            agg = aggregate(urls, timeout=timeout)
            ingest_aggregate(store, agg)
            last_up = sum(1 for ep in agg["endpoints"] if ep["up"])
            rep = interval_report(store, window=period * 1.5)
            if as_json:
                out(json.dumps(rep, sort_keys=True))
            else:
                out(format_interval(rep))
            n += 1
            if iterations and n >= iterations:
                break
            _time.sleep(period)
    except KeyboardInterrupt:
        pass
    return 0 if last_up else 2
