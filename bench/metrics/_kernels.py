"""Shared arithmetic of the kernel roofline readers: the bytes and
operations each solver round's kernel calls need, from the program's
``round_program.advance`` spans (bucket shape) and ``round_program.round``
spans (auction iterations, one bid call each)."""

import kernel_cost


def calls(spans):
    """``[(bucket_tasks, iterations), ...]`` per solver round."""
    out, bucket = [], None
    for s in spans:
        if s.name == "round_program.advance":
            bucket = int(s.args["bucket_tasks"])
        elif s.name == "round_program.round" and bucket is not None:
            out.append((bucket, int(s.args["iterations"])))
    return out


def roofline(o, kernel, bytes_fn, flops_fn, per_iteration):
    """Share (%) of the roofline time in the kernel's measured time."""
    if o.spans is None or o.device is None:
        return None
    t = o.device["kernel_s"].get(kernel, 0.0)
    rounds = calls(o.spans)
    if t <= 0 or not rounds:
        return None
    M = o.n_machines
    n = [(b, it if per_iteration else 1) for b, it in rounds]
    need_b = sum(k * bytes_fn(b, M) for b, k in n)
    need_f = sum(k * flops_fn(b, M) for b, k in n)
    least, _bound = kernel_cost.roofline_s(need_b, need_f, o.peak)
    return 100.0 * least / t
