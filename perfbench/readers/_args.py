"""Resolve a metric file's arguments: `"counter:<name>"` and
`"shape:<name>"` are looked up in the run's evidence, anything else is a
literal."""


def resolve(value, evidence):
    if isinstance(value, str) and value.startswith("counter:"):
        return evidence.counters[value[len("counter:"):]]
    if isinstance(value, str) and value.startswith("shape:"):
        return evidence.shapes[value[len("shape:"):]]
    return value


def resolve_all(args: dict, evidence) -> dict:
    return {k: resolve(v, evidence) for k, v in args.items()}
