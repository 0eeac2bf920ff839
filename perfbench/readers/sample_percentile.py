"""A percentile of samples the harness took on the host's clock around
its calls into the program (`spec["samples"]`, `spec["q"]`)."""
from perfbench.harness import percentile


def read(spec, evidence):
    values = evidence.samples.get(spec["samples"])
    if not values:
        return None
    return percentile(values, spec["q"]) * spec.get("scale", 1.0)
