"""The store's share of a solver step: device time of the operations
under the named scope ``store.put`` over that of every operation of the
fused producer programs (``jit_capture_scan_multi_impl``: solver steps
and their puts), control flow left out.  The scope is the ``op_name`` of
the instruction in the program's HLO; a fusion has its root
instruction's."""

from bench import spans as S


def read(ctx):
    return S.scope_share(ctx.trace, S.scopes_of(ctx), "store.put",
                         "capture_scan_multi_impl")
