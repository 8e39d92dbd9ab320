"""KV cache: device time of the paging programs per decode step, in the
traced part of the window (device trace).  The program compiles each of
them as a module of its own name (``jit_kv.assemble``,
``jit_kv.write_blocks``, ``jit_kv.slot_scatter``, ``jit_kv.zero_pages``,
``jit_kv.copy_page``); the decode steps are the ``jit_step`` runs matched
to decode calls.  None where the trace holds no program so named."""
PREFIX = "jit_kv."


def read(ctx):
    runs = (ctx.get("trace") or {}).get("module_runs") or []
    steps = (ctx.get("traced_calls") or {}).get("decode_steps")
    s = sum(dur for name, dur in runs if name.startswith(PREFIX))
    if not steps or not s:
        return None
    return 1e3 * s / steps
