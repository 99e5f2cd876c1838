"""Roofline share of the embedding-bag kernels in training (forward
``embedding_bag_fwd`` and backward ``embedding_bag_bwd_rows``, one of each
per sparse field and step): the least time the chip needs for their useful
bytes over the traced steps, over the kernels' summed device time.

Useful bytes, from the model wiring's ``bag_bytes_fwd`` / ``bag_bytes_bwd``:
forward, one table row read per valid id and one pooled row written per
bag; backward, each bag's cotangent row read and one gradient row written
per valid id. Their FLOPs are negligible, so the bound is the bytes over the
HBM bandwidth. Kernels are told apart by their names in the HLO text
(``KERNELS``), under JAX's transform names."""

KERNELS = r"%\w*embedding_bag_(?:fwd|bwd_rows)\w*\.\d+ = "


def read(ctx):
    t, out = ctx["trace"], ctx["outcome"]
    steps = out.counts.get("steps")
    if t is None or not steps:
        return None
    kernel_s = t.kernel_s(KERNELS)
    if not kernel_s:
        return None
    m = ctx["model"]
    nbytes = sum(m.bag_bytes_fwd(b) + m.bag_bytes_bwd(b) for b in steps)
    least = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (kernel_s * t.n_devices)
