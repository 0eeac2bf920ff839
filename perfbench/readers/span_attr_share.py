"""The share, in percent, of one of the program's spans recorded in the
traced sub-window (`spec["span"]`) whose attribute `spec["attr"]` equals
`spec["equals"]`: `data.next` spans with `depth` 0 are the steps that
found the prefetch queue empty, the feeder behind. 0 when none did."""
from perfbench.harness import log, median
from perfbench.readers import _spans


def read(spec, evidence):
    records = _spans.program_log()
    if records is None or evidence.trace is None:
        return None
    spans = _spans.captured(records, spec["span"])
    if not spans:
        return None
    hits = [r for r in spans if r.attrs.get(spec["attr"]) == spec["equals"]]
    seen = sorted({r.attrs.get(spec["attr"]) for r in spans}, key=str)
    wait_ms = median([_spans.ms(r.duration_ns) for r in spans])
    log(f"{spec['span']}: {len(hits)} of {len(spans)} spans of the traced "
        f"sub-window had {spec['attr']} == {spec['equals']} (values seen: "
        f"{seen}); median duration {wait_ms:.4f} ms")
    return 100.0 * len(hits) / len(spans)
