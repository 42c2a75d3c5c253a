"""A kernel's share of its roofline: the least time the chip could take for
the work the traced steps needed (``benchmark/flops.py::<ops_bytes>`` on the
job's shapes, against the peaks of ``benchmark/peaks.py``) over the device
time of the operations matching ``pattern``. Prints which bound applies."""

from benchmark import flops
from benchmark import trace as tr


def reduce(obs, args):
    if not obs.get("trace") or not tr.device_planes(obs["trace"]):
        return None
    seconds = tr.op_seconds(obs["trace"], args["pattern"])
    if seconds <= 0:
        return None
    kwargs = {k: obs["shapes"][v] for k, v in args.get("from_shapes", {}).items()}
    kwargs.update({k: obs["model"][v] for k, v in args.get("from_model", {}).items()})
    kwargs.update(args.get("fixed", {}))
    work = getattr(flops, args["ops_bytes"])(**kwargs)
    # the work of all chips runs on each chip's share of them
    least = flops.roofline_seconds(work, obs["peaks"])
    obs["log"](f"roofline {args['pattern']!r}: {work['ops']:.4g} ops, {work['bytes']:.4g} bytes, "
               f"least {least['seconds'] / obs['chips']:.6f} s/chip ({least['bound']}-bound), "
               f"measured {seconds:.6f} s")
    return 100.0 * least["seconds"] / obs["chips"] / seconds
