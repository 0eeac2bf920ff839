"""Pages reserved and not yet written, as a share of the pages reserved:
the mean over the `serve.schedule` spans of the WHOLE measured window
(`_window.py`) of (`pages_reserved` - `pages_filled`) / `pages_reserved`.
The scheduler reserves a request's worst-case span at admission
(`Scheduler._reserve_pages`); a page counts as filled from its first written
position. This is the memory that reservation by expected span, or pages
that grow with the request, would hand to another request.

It logs the means of both counts and their range. A tick with nothing
reserved is left out. None where the spans carry no counts (an older
commit)."""
from perfbench.harness import log
from perfbench.readers import _window


def read(spec, evidence):
    window = _window.find(evidence)
    if window is None:
        return None
    rows = [(r.attrs["pages_reserved"], r.attrs["pages_filled"])
            for v in window.children("serve.schedule").values() for r in v
            if r.attrs.get("pages_reserved", 0) > 0]
    if not rows:
        return None
    shares = [100.0 * (res - fil) / res for res, fil in rows]
    n = len(rows)
    log(window.describe())
    log(f"pages: over {n} ticks of the window a mean of "
        f"{sum(r for r, _ in rows) / n:.2f} reserved (range "
        f"{min(r for r, _ in rows)}-{max(r for r, _ in rows)}), "
        f"{sum(f for _, f in rows) / n:.2f} of them filled; unfilled share "
        f"min {min(shares):.2f}, mean {sum(shares) / n:.2f}, max "
        f"{max(shares):.2f}%")
    return sum(shares) / n
