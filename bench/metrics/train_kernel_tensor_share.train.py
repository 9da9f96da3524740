"""Share of the fused epoch's device time spent building the QuadConv
kernel tensor ``G`` (filter MLP and window, forward and its transposes
under differentiation): operations under the named scope
``quadconv.kernel_tensor`` over every operation of the fused-epoch
programs (``jit_epoch``), control flow left out.  The scope is the
``op_name`` of the instruction in the program's HLO; a fusion has its
root instruction's."""

from bench import spans as S


def read(ctx):
    return S.scope_share(ctx.trace, S.scopes_of(ctx), "quadconv.kernel_tensor",
                         "epoch")
