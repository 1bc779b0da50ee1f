"""KV pools: share of the device's busy time, inside the whole decode
steps of the traced slice, spent moving a hybrid model's per-session
state instead of computing on it, in percent: operations under
``sflm.kv_write`` and ``sflm.kv_read`` (the full layer's rows: one
scatter, one gather of every lane's rows) and under ``sflm.state_write``
and ``sflm.state_read`` (the window layers' rings and the Mamba layers'
recurrent rows).  The step's operations that carry no scope
(``unscoped:jit__step``) are printed beside it on the line before the
result (``trace.hybrid_state``) and are NOT added: what they are has to
be read from the trace before they are called the pool's (PERF.md
section 7, on ``kv_copy_device_share``).  A program whose step names no
``sflm.state_*`` scope has no such state: nothing to read."""

from benchmarks import spans

STATE_SCOPES = ("sflm.state_write", "sflm.state_read")


def read(run):
    got = spans.stepped(run)
    if got is None or got["busy_s"] <= 0:
        return None
    by_scope = got["device_by_scope"]
    if not any(name in by_scope for name in STATE_SCOPES):
        return None
    parts = {name: by_scope.get(name, 0.0)
             for name in spans.KV_SCOPES + STATE_SCOPES}
    unscoped = f"{spans.UNSCOPED}:{spans.STEP_MODULE}"
    run.trace["hybrid_state"] = {
        "ms_per_step": {k: v * 1e3 / got["steps"]
                        for k, v in parts.items()},
        unscoped + "_ms_per_step":
            by_scope.get(unscoped, 0.0) * 1e3 / got["steps"],
        unscoped + "_share_of_busy":
            100.0 * by_scope.get(unscoped, 0.0) / got["busy_s"]}
    return 100.0 * sum(parts.values()) / got["busy_s"]
