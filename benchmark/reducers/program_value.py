"""What the reducers that read the program's own metrics registry share: a
counter or a gauge as it stands when the run is reduced. None where the
program has no such instrument (an older program), never an error."""


def read(name: str):
    try:
        from veomni_tpu.observability.metrics import get_registry

        found = get_registry().get(name)
        return None if found is None else float(found.value)
    except Exception:
        return None


def ratio(over: str, under: str):
    """``over`` over ``under``, both the program's; None without either."""
    a, b = read(over), read(under)
    return None if a is None or not b else a / b


def reduce(obs, args):
    """``counter`` over ``over`` (both the program's), times ``scale``."""
    value = ratio(args["counter"], args["over"])
    return None if value is None else value * args.get("scale", 1.0)
