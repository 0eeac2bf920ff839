"""The share, in percent, of one of the program's spans over the ticks of
the WHOLE measured window (`_window.py`; `spec["span"]`, a child of
`serve.tick`) whose attribute `spec["attr"]` equals `spec["equals"]`:
`serve.schedule` spans whose `blocked` is `pages` are the ticks in which a
slot stood free and no waiting request's worst-case span of pages fitted.
0 when none did. It logs every value seen with its count. None where the
span carries no such attribute (an older commit)."""
from perfbench.harness import log
from perfbench.readers import _window


def read(spec, evidence):
    window = _window.find(evidence)
    if window is None:
        return None
    spans = [r for v in window.children(spec["span"]).values() for r in v
             if spec["attr"] in r.attrs]
    if not spans:
        return None
    seen = {}
    for r in spans:
        seen[r.attrs[spec["attr"]]] = seen.get(r.attrs[spec["attr"]], 0) + 1
    hits = seen.get(spec["equals"], 0)
    log(window.describe())
    log(f"{spec['span']}: {hits} of {len(spans)} spans of the window had "
        f"{spec['attr']} == {spec['equals']!r} (values seen: {seen})")
    return 100.0 * hits / len(spans)
