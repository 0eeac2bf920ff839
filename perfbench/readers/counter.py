"""A count or ratio the harness kept during the window
(`spec["counter"]`), or that the program's own telemetry kept."""


def read(spec, evidence):
    value = evidence.counters.get(spec["counter"])
    if value is None:
        return None
    return value * spec.get("scale", 1.0)
