#!/usr/bin/env python
"""Render query-trace profiles as Chrome/Perfetto trace-event JSON.

Input: either the JSONL sink (``spark.rapids.tpu.trace.sink.path`` —
one query profile per line) or a stitched ``PlanClient.last_trace()``
dump saved as JSON (``{"queryId": ..., "profiles": [...]}``). Output:
the Trace Event Format's JSON-array form — load it in
``chrome://tracing`` or https://ui.perfetto.dev and a fleet query reads
as ONE timeline: the client, router, and worker legs appear as separate
"processes" (tracks) whose spans all carry the same minted query_id.

Mapping:

- every profile becomes one pid (track) named ``component queryId``
  via ``process_name`` metadata events;
- every span becomes one complete ("ph": "X") event: ``ts``/``dur`` in
  microseconds — ``ts`` is the span's wall-clock open instant, so legs
  from different processes on one host line up (cross-host skew shifts
  whole tracks, never distorts durations);
- nesting rides the span's recorded parent chain: each span is placed
  on the tid of its depth so overlapping siblings (writer-pool /
  fetch-pool work) render side by side instead of fused;
- span attrs land in ``args`` (peer addresses, byte counts, cache
  outcomes, failover verdicts).

Usage:
    python tools/trace_viewer.py --table trace.jsonl   # where the time went
    python tools/trace_viewer.py trace.jsonl -o timeline.json
    python tools/trace_viewer.py --query-id 1234abcd trace.jsonl
    python tools/trace_viewer.py last_trace.json   # stitched dump

Exit 0 on success; the output is always a VALID trace-event JSON array
(the acceptance check loads it back and verifies the required keys).
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, Iterable, List, Optional


def load_profiles(path: str) -> List[dict]:
    """Accept the JSONL sink (one profile per line) or a stitched
    last_trace() dump ({"profiles": [...]}) or a bare profile/array."""
    with open(path, "r", encoding="utf-8") as f:
        text = f.read()
    stripped = text.lstrip()
    if stripped.startswith("{") or stripped.startswith("["):
        try:
            doc = json.loads(text)
            if isinstance(doc, dict) and "profiles" in doc:
                return list(doc["profiles"])
            if isinstance(doc, dict) and "spans" in doc:
                return [doc]
            if isinstance(doc, list):
                return list(doc)
        except json.JSONDecodeError:
            pass    # fall through to JSONL
    out = []
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        out.append(json.loads(line))
    return out


def _depths(spans: List[dict]) -> Dict[int, int]:
    """Span id -> nesting depth (root spans at 0); a missing parent
    (dropped past the span budget) renders at the root level."""
    by_id = {s["id"]: s for s in spans}
    memo: Dict[int, int] = {}

    def depth(sid: int) -> int:
        if sid in memo:
            return memo[sid]
        s = by_id.get(sid)
        parent = s.get("parent") if s else None
        d = 0 if not parent or parent not in by_id \
            else depth(parent) + 1
        memo[sid] = d
        return d

    for s in spans:
        depth(s["id"])
    return memo


def to_trace_events(profiles: Iterable[dict],
                    query_id: Optional[str] = None) -> List[dict]:
    events: List[dict] = []
    for pid, prof in enumerate(profiles, start=1):
        if query_id and prof.get("queryId") != query_id:
            continue
        label = f"{prof.get('component', 'engine')} " \
                f"{prof.get('queryId', '?')}"
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "tid": 0, "args": {"name": label}})
        spans = prof.get("spans", [])
        depths = _depths(spans)
        for s in spans:
            args = dict(s.get("attrs") or {})
            if "selfUs" in s:
                args["selfUs"] = s["selfUs"]
            args["queryId"] = prof.get("queryId")
            args["kind"] = s.get("kind", "span")
            events.append({
                "name": s["name"],
                "cat": s.get("kind", "span"),
                "ph": "X",
                "ts": int(s.get("tsUs", 0)),
                "dur": max(1, int(s.get("durUs") or 0)),
                "pid": pid,
                "tid": depths.get(s["id"], 0),
                "args": args,
            })
    return events


#: the counters of a rollup and its window: a table of their own, printed
#: only where a span carries one
_ROLLUP_WINDOW = ("expandProjections", "expandBatchesOut", "expandSlotsOut",
                  "rollupLevels", "rollupSlotsMerged",
                  "keyBatchRowsIn", "keyBatchSlotsSorted", "keyBatchCuts",
                  "windowBatches", "windowSlots", "windowExprs")

#: the eager concatenations (``exec/common._concat``) of a span: its own
#: table too
_CONCAT = ("concatCalls", "concatPieces", "concatSlotsOut")

#: a hash join's probes (``exec/join.HashJoinExec``): a fourth table
_JOIN = ("joinProbeBatches", "joinCandidates", "joinSlotsOut",
         "joinMappedSlots", "graceBuckets", "joinSearchRounds",
         "joinSearchRoundsPlain")


#: a read of a device value by the host (``trace.host_read``): a fifth table
_HOST_READS = ("hostReads", "hostReadUs")


#: span attributes that are counts: a name's row sums them over its spans
_SUMMED = ("pulls", "lowerings", "programHits", "programMisses",
           "dec128Columns", "dec128Bytes",
           "partialsCut", "partialRowsMade", "partialRowsKept",
           "splitBatches", "splitPieces", "splitRowsSorted",
           "splitRowsGathered") + _ROLLUP_WINDOW + _CONCAT + _JOIN


def self_time_table(profile: dict) -> List[dict]:
    """One row per span name of one profile, largest own time first:
    spans, their time inside (an operator's pulls, any other span's
    duration), their OWN time (``trace.self_times``: less the children
    on the same thread), pulls, what JAX did under them (lowerings, and
    the milliseconds of ``jit.*`` spans directly below), how many of
    their execs' keyed programs the program table already had, and the
    decimal128 limb columns they emitted with their device bytes
    (``dec128Columns`` / ``dec128Bytes``), and the aggregates' partials:
    how many were cut to their groups' bucket and the summed capacities
    before and after (``partialsCut`` / ``partialRowsMade`` /
    ``partialRowsKept``), and the exchanges' splits: batches split, pieces
    made, slots ordered (a batch's capacity, once) and slots gathered (the
    pieces' summed capacities) (``splitBatches`` / ``splitPieces`` /
    ``splitRowsSorted`` / ``splitRowsGathered``), and a rollup and its
    window (``print_tables``' second table): the projections an Expand
    makes, the batches and slots it hands on (``expandProjections`` /
    ``expandBatchesOut`` / ``expandSlotsOut``), the levels a ``RollupExec``
    merged from partials and the slots it merged them at (``rollupLevels``
    / ``rollupSlotsMerged``), the rows a ``KeyBatchingExec`` took in, the
    slots it had to sort and the cuts it made (``keyBatchRowsIn`` /
    ``keyBatchSlotsSorted`` / ``keyBatchCuts``), and a ``WindowExec``'s
    batches, their slots and its expressions (``windowBatches`` /
    ``windowSlots`` / ``windowExprs``), and the eager concatenations
    (a third table): calls, the pieces they wrote, their summed output
    slots (``concatCalls`` / ``concatPieces`` / ``concatSlotsOut``), and a
    hash join's probes (a fourth table): probe batches, their summed
    candidate pairs, the summed slots of the pair batches made for them
    and those of them whose probe rows came from the one-pass map rather
    than a search, the buckets of a grace join, and the probes' search
    rounds, summed over probe batches: those the build's directory asked
    for (0 on a dense build) and those a search of the whole build would
    have taken (``joinProbeBatches`` / ``joinCandidates`` /
    ``joinSlotsOut`` / ``joinMappedSlots`` / ``graceBuckets`` /
    ``joinSearchRounds`` / ``joinSearchRoundsPlain``).
    The host's reads of device values are a fifth table
    (``host_read_table``)."""
    import os
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), os.pardir))
    from spark_rapids_tpu.trace import inside_us, self_times
    spans = profile.get("spans", [])
    own = self_times(spans)
    name_of = {s["id"]: s["name"] for s in spans}
    rows: Dict[str, dict] = {}
    for s in spans:
        attrs = s.get("attrs") or {}
        r = rows.setdefault(s["name"], {
            "name": s["name"], "spans": 0, "insideMs": 0.0, "selfMs": 0.0,
            "relowerMs": 0.0, **dict.fromkeys(_SUMMED, 0)})
        r["spans"] += 1
        r["insideMs"] += inside_us(s) / 1000.0
        r["selfMs"] += own[s["id"]] / 1000.0
        for k in _SUMMED:
            r[k] += int(attrs.get(k, 0))
        parent = name_of.get(s.get("parent"))
        if s["name"].startswith("jit.") and parent in rows \
                and not parent.startswith("jit."):
            rows[parent]["relowerMs"] += (s.get("durUs") or 0) / 1000.0
    return sorted(rows.values(), key=lambda r: -r["selfMs"])


def host_read_table(profile: dict) -> List[dict]:
    """One row per span name under which the host read a device value,
    most microseconds first: the reads and their microseconds
    (``hostReads`` / ``hostReadUs``, counted on the innermost span or pull
    frame: an operator, or a span such as an exchange's ``<Exec>.write``)
    and their share of the time inside those spans (``trace.inside_us``:
    an operator's ``pullUs``)."""
    from spark_rapids_tpu.trace import inside_us
    rows: Dict[str, dict] = {}
    for s in profile.get("spans", []):
        attrs = s.get("attrs") or {}
        if not attrs.get("hostReads"):
            continue
        r = rows.setdefault(s["name"], {"name": s["name"], "hostReads": 0,
                                        "hostReadUs": 0, "insideUs": 0})
        for k in _HOST_READS:
            r[k] += int(attrs.get(k, 0))
        r["insideUs"] += inside_us(s)
    return sorted(rows.values(), key=lambda r: -r["hostReadUs"])


def print_tables(profiles: Iterable[dict],
                 query_id: Optional[str] = None) -> None:
    for prof in profiles:
        if query_id and prof.get("queryId") != query_id:
            continue
        print(f"# {prof.get('component', 'engine')} "
              f"{prof.get('queryId', '?')}: "
              f"{(prof.get('durUs') or 0) / 1000.0:.1f} ms")
        print(f"{'span':<34}{'n':>4}{'inside ms':>12}{'self ms':>12}"
              f"{'pulls':>7}{'lowerings':>10}{'relower ms':>12}"
              f"{'hit/miss':>10}{'dec128 cols':>12}{'dec128 bytes':>14}"
              f"{'splits':>8}{'pieces':>8}{'slots sorted':>14}"
              f"{'slots gathered':>16}"
              f"{'partials cut':>13}{'rows made':>11}{'rows kept':>11}")
        table = self_time_table(prof)
        for r in table:
            print(f"{r['name']:<34}{r['spans']:>4}{r['insideMs']:>12.1f}"
                  f"{r['selfMs']:>12.1f}{r['pulls']:>7}"
                  f"{r['lowerings']:>10}{r['relowerMs']:>12.1f}"
                  f"{str(r['programHits']) + '/' + str(r['programMisses']):>10}"
                  f"{r['dec128Columns']:>12}{r['dec128Bytes']:>14}"
                  f"{r['splitBatches']:>8}{r['splitPieces']:>8}"
                  f"{r['splitRowsSorted']:>14}"
                  f"{r['splitRowsGathered']:>16}"
                  f"{r['partialsCut']:>13}{r['partialRowsMade']:>11}"
                  f"{r['partialRowsKept']:>11}")
        for keys in (_ROLLUP_WINDOW, _CONCAT, _JOIN):
            rolled = [r for r in table if any(r[k] for k in keys)]
            if rolled:
                print(f"{'span':<34}" + "".join(
                    f"{k:>{len(k) + 2}}" for k in keys))
                for r in rolled:
                    print(f"{r['name']:<34}" + "".join(
                        f"{r[k]:>{len(k) + 2}}" for k in keys))
        reads = host_read_table(prof)
        if reads:
            print(f"{'span':<34}{'hostReads':>11}{'hostReadUs':>12}"
                  f"{'of inside':>11}")
            for r in reads:
                share = f"{100.0 * r['hostReadUs'] / r['insideUs']:.1f} %" \
                    if r["insideUs"] else "-"
                print(f"{r['name']:<34}{r['hostReads']:>11}"
                      f"{r['hostReadUs']:>12}{share:>11}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="query-trace profiles -> Chrome trace-event JSON")
    p.add_argument("input", help="JSONL sink file or stitched "
                                 "last_trace() JSON dump")
    p.add_argument("-o", "--out", default=None,
                   help="output path (default: stdout)")
    p.add_argument("--query-id", default=None,
                   help="render only this query's profiles")
    p.add_argument("--table", action="store_true",
                   help="print each profile's self-time table (own ms, "
                        "pulls, lowerings by span name) instead")
    args = p.parse_args(argv)
    profiles = load_profiles(args.input)
    if args.table:
        print_tables(profiles, query_id=args.query_id)
        return 0
    events = to_trace_events(profiles, query_id=args.query_id)
    blob = json.dumps(events, indent=1)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(blob)
        print(f"wrote {len(events)} trace events to {args.out}",
              file=sys.stderr)
    else:
        print(blob)
    return 0


if __name__ == "__main__":
    sys.exit(main())
