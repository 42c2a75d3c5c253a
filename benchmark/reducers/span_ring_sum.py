"""Seconds of a named span summed over the process's life, from the
program's span ring (set-up spans end before the window starts, so
``obs["spans"]`` does not hold them). None where the ring holds none."""


def reduce(obs, args):
    try:
        from veomni_tpu.observability import spans

        durs = [dur for name, _t0, dur, _tid in spans.live_span_events() if name == args["span"]]
    except Exception:
        return None
    if not durs:
        return None
    obs["log"](f"span {args['span']}: {len(durs)} spans, {sum(durs) * 1e-9:.3f} s in all")
    return sum(durs) * 1e-9
