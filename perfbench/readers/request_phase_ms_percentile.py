"""A percentile, in milliseconds, of one phase of a request's life
(`spec["span"]`: `request.queued`, `request.prefill`; `spec["q"]`) over the
phases that CLOSED inside the measured window (`_window.py`), from the
records the program keeps of each request (`request=<id>` on each). With
`"begun_inside"` only those that also BEGAN inside it: the first wave is
admitted in set-up and its rows' first tokens reach the host in the window's
first tick, so their `request.prefill` is set-up's batch of calls, not an
admission of the window's.

SURVIVORS ONLY. A phase is recorded when it closes, so a request that still
waits when the window closes has left no `request.queued`: where the queue
grows through the window the percentile is that of the waits that ended,
and the longest are not among them. With `"account"` the reader says how
many: `serve.schedule`'s `waiting` in the window's last tick is the number
of waits the close cut off, and its mean over the ticks is the queue's mean
length, which it sets beside the slots the harness saw standing empty and
beside Little's law on the waits that ended (admissions a second times
their mean). Where the three agree the percentile stands for the queue;
where Little's law reads low it is the survivors'.

A phase is long (a wait of seconds) and in a traced run the profiler's stop
holds the host for 10-18 s, so nearly every phase reaches into a hold.
Leaving those out keeps only the short ones; instead each is read on the
engine's own time: its duration less the part in which the profiler held
the host between two ticks (`Window.held_ns`), when no request could move.

It logs how many it read, how many it shortened and by how much, the values
in the order the phases closed (a queue that grows shows here) and every
attribute the phases carry (`blocked_on`; `calls`, `cached_tokens`). With
`"account"` also the whole requests that finished in the window, from their
`request` roots: `finish_reason`, `prompt_len`, `tokens` and
`pages_reserved`, whether each root is the sum of its phases to the
nanosecond, and from `request.decode` the time a token took. Fewer than
`FEW` phases are reported all the same, with a warning line: a median of
three is three numbers, not a distribution.

None where the program records no requests (an older commit).
"""
from perfbench.harness import log, median, percentile
from perfbench.readers import _spans, _window

FEW = 5


def attr_lines(records, what):
    """A line an attribute that `records` carry beside `request`: counts by
    value where the values are few, else their range and mean."""
    lines = []
    for key in sorted({k for r in records for k in r.attrs} - {"request"}):
        values = [r.attrs[key] for r in records if key in r.attrs]
        counts = {}
        for v in values:
            counts[v] = counts.get(v, 0) + 1
        if len(counts) <= 6 or isinstance(values[0], str):
            said = str(dict(sorted(counts.items(), key=lambda kv: -kv[1])))
        else:
            said = (f"min {min(values)}, mean {sum(values) / len(values):.1f},"
                    f" max {max(values)}")
        lines.append(f"  {what} {key}: {said}")
    return lines


def queue_lines(window, waits_ns, evidence):
    """The queue's length from three sides: `waiting` on the window's
    `serve.schedule` spans, Little's law on the waits that ended, and the
    slots that stood empty by the harness's own count."""
    seconds = window.seconds - window.held_ns(window.start_ns,
                                              window.end_ns) / 1e9
    rate = len(waits_ns) / seconds
    wait_s = sum(waits_ns) / 1e9 / len(waits_ns)
    lines = [f"  Little's law: {len(waits_ns)} admissions in {seconds:.3f} s "
             f"of the engine's time = {rate:.4f} a second x mean wait "
             f"{wait_s:.4f} s = {rate * wait_s:.3f} requests waiting"]
    scheduled = sorted((r for v in window.children("serve.schedule").values()
                        for r in v if "waiting" in r.attrs),
                       key=lambda r: r.start_ns)
    if scheduled:
        waiting = [r.attrs["waiting"] for r in scheduled]
        mean = sum(waiting) / len(waiting)
        lines.append(
            f"  the queue itself (serve.schedule's waiting, after each of "
            f"{len(waiting)} ticks' admissions): {waiting[0]} when the "
            f"window opens, mean {mean:.3f}, {waiting[-1]} when it closes: "
            f"{waiting[-1]} waits cut off by the close have left no record, "
            f"beside the {len(waits_ns)} read"
            + (f"; a queue of that mean length at that rate is a wait of "
               f"{mean / rate:.3f} s (Little's law reads "
               f"{rate * wait_s / mean:.3f} of it: under 0.8 the percentile "
               f"is the survivors')" if mean > 0 else ""))
    occupied = evidence.counters.get("serve.slot_occupancy_pct")
    slots = evidence.shapes.get("slots")
    if occupied is not None and slots:
        empty = slots * (1.0 - occupied / 100.0)
        line = (f"  the harness saw {empty:.3f} of {slots} slots empty, "
                f"mean over ticks")
        if empty > 0:
            line += f" (Little's law over it: {rate * wait_s / empty:.3f}"
            if scheduled:
                line += f", the queue's mean over it: {mean / empty:.3f}"
            line += ")"
        lines.append(line)
    return lines


def request_lines(window):
    """The whole requests that finished inside the window, from their
    roots and the phases under each."""
    roots = window.closed_inside("request")
    if not roots:
        return ["  no request finished inside the window"]
    kids = {}
    for r in window.records:
        if r.thread == 0 and r.name.startswith("request."):
            kids.setdefault(r.parent, []).append(r)
    off = [abs(root.duration_ns - sum(k.duration_ns
                                      for k in kids.get(root.id, ())))
           for root in roots]
    lines = [f"  {len(roots)} requests finished inside the window; the "
             f"phases of {sum(1 for d in off if d == 0)} sum to their root "
             f"to the nanosecond (largest difference {max(off)} ns)"]
    lines += attr_lines(roots, "their")
    per_token = []
    for root in roots:
        for k in kids.get(root.id, ()):
            if k.name == "request.decode" and root.attrs.get("tokens", 0) > 1:
                took = k.duration_ns - window.held_ns(k.start_ns, k.end_ns)
                per_token.append(_spans.ms(took) / (root.attrs["tokens"] - 1))
    if per_token:
        lines.append(f"  request.decode: a token took min "
                     f"{min(per_token):.3f}, median {median(per_token):.3f},"
                     f" max {max(per_token):.3f} ms over {len(per_token)} "
                     f"requests, on the engine's time")
    return lines


def read(spec, evidence):
    window = _window.find(evidence)
    if window is None:
        return None
    name = spec["span"]
    if not any(r.name == name for r in window.records):
        return None
    closed = window.closed_inside(name)
    log(window.describe())
    line = f"{name}: {len(closed)} closed inside the window"
    if spec.get("begun_inside"):
        closed = [r for r in closed if r.start_ns >= window.start_ns]
        line += f", {len(closed)} of them begun inside it too"
    if not closed:
        log(line)
        return None
    held = [window.held_ns(r.start_ns, r.end_ns) for r in closed]
    ns = [r.duration_ns - h for r, h in zip(closed, held)]
    log(f"{line}; {sum(1 for h in held if h > 0)} reached into a profiler "
        f"hold and are read less {_spans.ms(sum(held)):.1f} ms of it in all")
    for line in attr_lines(closed, "their"):
        log(line)
    if spec.get("account"):
        for line in queue_lines(window, ns, evidence) + request_lines(window):
            log(line)
    values = [_spans.ms(v) for v in ns]
    log(f"  {len(values)} read: min {min(values):.3f}, p50 "
        f"{percentile(values, 50):.3f}, p90 {percentile(values, 90):.3f}, "
        f"max {max(values):.3f} ms; in the order they closed: "
        + " ".join(f"{v:.0f}" for v in values))
    if len(values) < FEW:
        log(f"  WARNING: a percentile of {len(values)} requests")
    return percentile(values, spec["q"])
