"""Device time per cycle of the ops under the update's named scope that
are not the ``stencil27`` kernel: the lane axis's wrap, the interior's
write-back and their fusions, averaged over the chips.  With
``update_roofline``'s time it splits the update into the kernel and the
copies around it."""


def read(ctx):
    if not ctx.cycles or ctx.trace.op_seconds(lambda op: op.in_scope) <= 0:
        return None
    seconds = ctx.trace.op_seconds(
        lambda op: op.in_scope and op.name.split(".")[0] != "stencil27")
    return seconds / ctx.cycles * 1e6
