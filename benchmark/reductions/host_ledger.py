"""A number read from the program's host ledger (``monitor.host_records()``:
every trace, lowering, backend compile and compile-cache event by jitted
function, the host time under every ``monitor.spans.span``, every collector
pause of a millisecond or more; nanoseconds of ``time.perf_counter_ns``).

The **window** is the last ``ctx["steps"]`` events of the span ``STEP_CALL``,
the traced window's dispatches; **set-up** is what ended before the first of
them. From the metric's file: ``of`` (``setup`` | ``window``), ``kinds``, and
where given ``names`` (only these), ``inside`` (only events enclosed, on their
thread, by one of that kind) and ``exclude`` (drop these names: the
reference's ``run``, whose time ``setup_s`` leaves out too). Of what is chosen
only the outermost are read (a nested ``jit`` is traced inside its caller's
trace and a kernel's span opens inside its layer's: counted once), then
``exclude`` is applied, so that what an excluded entry encloses goes with it.
``read``: ``seconds`` (summed), ``count``, ``mean_ms``, ``longest_ms``.

A program without the ledger, or one that ran no ``STEP_CALL``, gives
nothing; a ledger with nothing of the kind in reach gives 0."""

STEP_CALL = "donate_step.call"


def outermost(records):
    """Those of ``records`` that no other of them encloses on the same thread
    (the program has the same few lines for its own summary; what a metric
    counts stays with the benchmark)."""
    out, reach = [], {}
    for r in sorted(records, key=lambda r: (r["start"], -r["end"])):
        if r["end"] > reach.get(r["tid"], -1):
            out.append(r)
            reach[r["tid"]] = r["end"]
    return out


def chosen(spec, records, steps):
    """The events of ``records`` that the metric ``spec`` reads, or ``None``
    where the ledger holds no step."""
    calls = [r for r in records if r["kind"] == "span" and r["name"] == STEP_CALL][-steps:]
    if not calls:
        return None
    first, last = calls[0]["start"], calls[-1]["end"]
    if spec["of"] == "setup":
        reach = [r for r in records if r["end"] <= first]
    else:
        reach = [r for r in records if first <= r["start"] and r["end"] <= last]
    events = [r for r in reach if r["kind"] in spec["kinds"]
              and ("names" not in spec or r["name"] in spec["names"])]
    if "inside" in spec:
        around = [r for r in reach if r["kind"] == spec["inside"]]
        events = [r for r in events if any(
            a["tid"] == r["tid"] and a["start"] <= r["start"] and r["end"] <= a["end"]
            for a in around)]
    return [r for r in outermost(events) if r["name"] not in spec.get("exclude", ())]


def reduce(spec, ctx):
    try:
        from beforeholiday_tpu.monitor import host_records
    except ImportError:
        return None
    events = chosen(spec, host_records(), ctx["steps"])
    if events is None:
        return None
    ns = [r["end"] - r["start"] for r in events]
    if spec["read"] == "count":
        return float(len(ns))
    if spec["read"] == "seconds":
        return sum(ns) * 1e-9
    if spec["read"] == "mean_ms":
        return sum(ns) * 1e-6 / len(ns) if ns else 0.0
    return max(ns, default=0) * 1e-6         # longest_ms
