"""Scheduler: what of a request's gap between tokens is other requests'
refills. Per request the program's span record gives ``decode_s`` (its
first token -> its last token delivered) and ``decode_in_refill_s`` (the
part of that interval that lay under OTHER requests' ``refill.*`` spans:
while a prefill runs, the seated slots decode nothing); pooled over the
window's requests with two tokens or more, the sum of the second over
the sum of the first. The rest of the interval is the request's own
chunks (``decode_in_chunk_s``: what ``step_decode_ms`` times) and the
host's work between spans. A burst in which the benchmark started or
stopped its profiler is left out: those pauses fall inside ``on_token``,
so inside a ``refill.seat`` and a ``chunk.deliver``, and are not the
program's. Nothing to read where the program keeps no record."""


def read(run):
    pauses = run["traced"][2] if run.get("traced") else ()
    part = whole = 0.0
    for b in run["bursts"]:
        m = b.outs.metrics
        spans = getattr(m, "spans", None)
        if not spans:
            return None
        if any(spans[0].t0 <= s and e <= spans[-1].t1 for s, e in pauses):
            continue
        for r in m.per_request:
            if r.new_tokens >= 2:
                part += r.decode_in_refill_s
                whole += r.decode_s
    return 100.0 * part / whole if whole else None
