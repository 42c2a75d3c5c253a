"""A gauge of the program's metrics registry, as it stands when the run is
reduced, times ``scale``. None where the program has no such gauge."""


def reduce(obs, args):
    try:
        from veomni_tpu.observability.metrics import get_registry

        gauge = get_registry().get(args["gauge"])
        return None if gauge is None else float(gauge.value) * args.get("scale", 1.0)
    except Exception:
        return None
