"""The grouped expert matmul's share of its roofline in the traced
stretch, counted by the experts its calls DID hit: the least time the
chip could take for the work of the kernel's calls inside one step
program (operations and bytes from the configuration's architecture
entry, `moe_kernel_hit(config, calls, hit_share, batch, chips)`: the
weights of the held experts that took a pair, and the pairs' rows; peaks
from benchmark/peaks.json) over the device time those calls took, in %.
`hit_share` is what ran: the engine's `moe_load` flight events (kind 19)
that landed inside the traced stretch carry, in `aux`, the (expert layer,
held expert, step) triples of their block that took a pair and all of
them (`hit=<n> of=<m>`), as `trace_window_kernel.py` takes its pages from
the `window_cache` events. `trace_moe_kernel.py` takes the hit experts as
EXPECTED under uniform routing, which a skewed seeded router undercuts.
An entry without the function, a program without the kernel, an engine
whose events carry no `hit` (every program from before it) or no trace
gives None."""
from benchmark import architectures
from benchmark.harness import roofline
from benchmark.readers import engine_moe_hit, trace_program


def traced_stretch(ctx):
    """(start, stop) of the traced stretch, seconds from the window's
    opening (trace_window_kernel.py's arithmetic)."""
    tr = ctx["traffic"].get("trace", {})
    seconds = ctx["seconds"]
    start = min(float(tr.get("start_s", 5.0)), seconds / 3)
    return start, start + min(float(tr.get("seconds", 4.0)), seconds / 3)


def read(ctx, program_name, kernel):
    tr = ctx["trace"]
    prog = trace_program.program(ctx, program_name)
    work_of = getattr(architectures.load(ctx["config"]), "moe_kernel_hit",
                      None)
    if not prog or work_of is None or not ctx["peaks"]:
        return None
    start, stop = traced_stretch(ctx)
    share = engine_moe_hit.hit_share(ctx["engine"]["events"],
                                     lambda e: start <= e["t"] <= stop)
    device_s = sum(s for k, s in tr["ops"].items()
                   if k.startswith(program_name + "/")
                   and kernel in k.split("/", 1)[1])
    calls = sum(n for kind, n in prog["kernel_calls"].items()
                if kernel in kind)
    a, b = ctx["engine"]["trace_open"], ctx["engine"]["trace_close"]
    d_steps = b["decode_steps"] - a["decode_steps"]
    if share is None or not device_s or not calls or not d_steps:
        return None
    batch = (b["busy_slots_acc"] - a["busy_slots_acc"]) / d_steps
    work = work_of(ctx["config"], calls, share, batch, ctx["chips"])
    return 100.0 * roofline.least_seconds(work, ctx["peaks"])["seconds"] \
        / device_s
