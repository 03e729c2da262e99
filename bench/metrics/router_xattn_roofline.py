"""Share of its roofline that the router's Pallas kernel reached.

Kernel time: the device durations of the ``_router_xattn_pool_jit``
custom-call events in the trace (the Mosaic kernel alone, without the
padding around it). Least time: per scoring call, the larger of its
operations over the peak FLOP/s and its bytes over the HBM bandwidth,
both counted at the logical (unpadded) shapes by the functions below.
At these shapes the bytes bound it.
"""
KERNEL = "_router_xattn_pool_jit"


def flops(b: int, d_query: int, latent: int, members: int) -> float:
    """Query projection, member logits, attended context, output head."""
    return 2.0 * b * (d_query * latent + 3 * latent * members)


def bytes_moved(b: int, d_query: int, latent: int, members: int) -> float:
    """float32 queries, weights, pool projections, bias and scores, each
    read or written once."""
    return 4.0 * (b * d_query + d_query * latent + 2 * members * latent
                  + latent * members + members + b * members)


def read(run):
    if run.trace is None:
        return None
    durations = run.trace.kernel_durations(KERNEL)
    calls = [s for s in run.rec.scores
             if s["kernel"] and s["t"] <= run.window.t_close]
    if not durations or not calls:
        return None
    shape = run.router_shapes
    least = 0.0
    for s in calls:
        args = (len(s["texts"]), shape["d_query"], shape["latent"],
                shape["members"])
        least += max(flops(*args) / run.peaks["flops_per_s"],
                     bytes_moved(*args) / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / sum(durations)
