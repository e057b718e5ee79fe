"""The GLOBAL rows' attention kernel's share of its roofline in the
traced stretch of a model with window rows beside them: the least time
the chip could take for the pages the kernel's calls WALKED (bytes and
operations from the configuration's architecture entry,
`global_attention_pages(config, pages, calls, batch, chips)`; peaks from
benchmark/peaks.json) over the device time those calls took, in %. The
calls are those of `kernel` whose name does not hold `exclude` (the
window rows' call is `paged_attention_int8_window`, the global rows'
`paged_attention_int8`). The PAGES are what ran: the engine's
`window_cache` flight events (kind 23) that landed inside the traced
stretch carry, in `aux`, the pages their block's global calls walked and
those calls (`global_pages=<n> global_calls=<m>`); their pages a call,
times the calls the trace holds, is the work, as `trace_window_kernel.py`
counts the window rows'. An entry without the function, a program without
the kernel, an engine whose events carry no `global_pages` (every program
from before it) or no trace gives None."""
from benchmark import architectures
from benchmark.harness import roofline
from benchmark.readers import trace_program
from benchmark.readers.trace_moe_hit_kernel import traced_stretch

WINDOW_CACHE = 23


def traced_pages_per_call(ctx):
    """Pages a global call walked over the traced stretch, or None."""
    start, stop = traced_stretch(ctx)
    pages = calls = 0
    for e in ctx["engine"]["events"]:
        if e["kind"] == WINDOW_CACHE and start <= e["t"] <= stop:
            aux = dict(kv.split("=", 1) for kv in e["aux"].split())
            pages += int(aux.get("global_pages", 0))
            calls += int(aux.get("global_calls", 0))
    return pages / calls if calls else None


def read(ctx, program_name, kernel, exclude):
    tr = ctx["trace"]
    prog = trace_program.program(ctx, program_name)
    work_of = getattr(architectures.load(ctx["config"]),
                      "global_attention_pages", None)
    if not prog or work_of is None or not ctx["peaks"]:
        return None

    def mine(name):
        return kernel in name and exclude not in name

    per_call = traced_pages_per_call(ctx)
    device_s = sum(s for k, s in tr["ops"].items()
                   if k.startswith(program_name + "/")
                   and mine(k.split("/", 1)[1]))
    calls = sum(n for kind, n in prog["kernel_calls"].items() if mine(kind))
    a, b = ctx["engine"]["trace_open"], ctx["engine"]["trace_close"]
    d_steps = b["decode_steps"] - a["decode_steps"]
    if per_call is None or not device_s or not calls or not d_steps:
        return None
    batch = (b["busy_slots_acc"] - a["busy_slots_acc"]) / d_steps
    work = work_of(ctx["config"], per_call * calls, calls, batch,
                   ctx["chips"])
    return 100.0 * roofline.least_seconds(work, ctx["peaks"])["seconds"] \
        / device_s
