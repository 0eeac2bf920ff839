"""Seconds of set-up spent in some of JAX's own phases (`spec["spans"]`:
`jax.trace` and `jax.lower`, or `jax.compile` and `jax.cache_load`), from
the spans the program records of them.

Counted: the spans that ended before the first program span of the traced
sub-window began — set-up and warm-up, the harness's own programs among
them (under no program span). JAX reports a function traced inside
another's trace, and an eager compile inside a trace, as spans of their
own; each instant counts once, for the innermost span that covers it. It
logs the seconds by enclosing program span, by the outermost span's
`fun_name` and by the span's own name (a `jax.cache_load` row is a program
found in the persistent cache, a `jax.compile` row one that was not), ten
largest; with `"program_spans"` also the program's own set-up spans, each
with its seconds and JAX's share of them, and how full the log is.
"""
from perfbench.harness import log
from perfbench.readers import _spans


def program_span_lines(records, setup, own, by_id):
    """The program's own set-up spans with their seconds and, of those,
    the seconds JAX reported under each: the rest is what jax.monitoring
    does not see (imports, `eval_shape`, transfers, the device)."""
    under = {}
    for r in setup:
        parent = r.parent
        while parent in by_id:
            under[parent] = under.get(parent, 0.0) + own[r.id]
            parent = by_id[parent].parent
    cutoff = max((r.end_ns for r in setup), default=0)
    rows = []
    for r in records:
        if (r.name in _spans.JAX_SPANS or r.in_capture
                or r.end_ns > cutoff or r.name in _spans.WINDOW_SPANS
                or "serve.tick" in _spans.path(r, by_id)):
            continue
        where = f"{_spans.path(r, by_id)}>" if r.parent in by_id else ""
        rows.append((f"{where}{r.name}: of which "
                     f"{under.get(r.id, 0.0) / 1e9:.3f} s in jax.*",
                     r.duration_ns / 1e9))
    return _spans.table("the program's set-up spans:", rows, "s")


def read(spec, evidence):
    records = _spans.program_log()
    if records is None or evidence.trace is None:
        return None
    setup = _spans.setup_spans(records)
    if setup is None:
        return None
    wanted = set(spec["spans"])
    own, root = _spans.self_times(setup)
    by_id = {r.id: r for r in records}
    total, rows = 0.0, {}
    for r in setup:
        if r.name not in wanted:
            continue
        total += own[r.id]
        outer = by_id[root[r.id]]
        key = (f"{_spans.path(outer, by_id)}: "
               f"{outer.attrs.get('fun_name', '?')} [{r.name}]")
        rows[key] = rows.get(key, 0.0) + own[r.id]
    if spec.get("program_spans"):
        log(f"span log: {len(records)} records, {len(setup)} of them JAX's "
            f"before the window's first captured span")
        for line in program_span_lines(records, setup, own, by_id):
            log(line)
    for line in _spans.table(
            f"set-up in {' + '.join(sorted(wanted))}: {total / 1e9:.3f} s "
            f"over {sum(1 for r in setup if r.name in wanted)} spans, by "
            f"enclosing program span, outermost fun_name and [span]:",
            [(k, v / 1e9) for k, v in rows.items()], "s"):
        log(line)
    return total / 1e9
