"""KV pools: share of the device's busy time, inside the whole decode
steps of the traced slice, spent moving the pool instead of computing
on it, in percent: operations under ``sflm.kv_write`` and
``sflm.kv_read`` (the scatter of one position per lane and the gather
of each lane's cache back out), plus the decode step's operations that
carry no scope at all (``unscoped:jit__step``).  Those are counted
because of what the trace shows there (chip run, PR 24): the compiler's
own re-layouts of the whole pool around every gather —
``fusion.remat_compressed``, ``fusion.remat_uncompressed`` and ``copy``
of the ``(slots+1, L, T, H, Dh)`` array, which have no JAX name — are
1.7505 s of its 1.7508 s; everything else unscoped in the step (the
weights' ``slice-done``) is 0.02 %.  Once the pool's layout is repaired
the term is that remainder."""

from benchmarks import spans


def read(run):
    got = spans.stepped(run)
    if got is None or got["busy_s"] <= 0:
        return None
    by_scope = got["device_by_scope"]
    if not any(name in by_scope for name in spans.KV_SCOPES):
        return None       # a program whose step names no scope
    moved = sum(by_scope.get(name, 0.0) for name in spans.KV_SCOPES
                + (f"{spans.UNSCOPED}:{spans.STEP_MODULE}",))
    return 100.0 * moved / got["busy_s"]
