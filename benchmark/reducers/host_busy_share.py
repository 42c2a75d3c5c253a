"""The share of the measured window in which the loop was NOT waiting for the
device: 100 x (loop - the ``wait_spans`` spans' durations) / loop, where
``loop`` (``obs["loop_s"]``) runs from the window's start to the loop's last
return inside it, the job's own closing sync left out, and the spans are the
program's own from its span ring (``obs["spans"]``: those that started in the
window). The waits follow one another in the loop (``step.dispatch``, then
``step.backpressure`` inside the supervisor's ``observe``), so their sum is
their union.

``step.dispatch`` is among the waits: where the device's memory is tight the
runtime blocks the host inside the dispatch until a step's buffers are free,
and ``step.backpressure`` never sees that wait (PERF.md, section 3).

Over the untraced window and not over the trace: the trace holds the run's
first steps after a sync, where the loop has not yet filled its in-flight
bound and waits nowhere but in the job's own sync, which is no span of the
program's (read there, the dense cell's host came out 99.9% busy: PR 43).
None where the program has no such span."""


def reduce(obs, args):
    waits = [d for name in args["wait_spans"] for d in obs["spans"].get(name, ())]
    if not waits or not obs.get("loop_s"):
        return None
    obs["log"](f"host: {len(waits)} {' / '.join(args['wait_spans'])} spans cover "
               f"{sum(waits):.4f} s of the {obs['loop_s']:.4f} s the loop ran in the window")
    return 100.0 * (1.0 - sum(waits) / obs["loop_s"])
