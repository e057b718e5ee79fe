"""The index-score kernel's share of its roofline in the traced window:
the least time the chip could take for the work of the kernel's calls
inside one step program (operations and bytes from the configuration's
architecture entry, `index_kernel(config, calls, batch, context, chips)`:
every cached index key of a live sequence read and scored once a call;
peaks from benchmark/peaks.json) over the device time those calls took,
in %. The CONTEXT is what the engine dispatched in the traced stretch:
the mean of the `sparse_select` flight events' `a` (kind 22: index keys
scored a live slot, step and layer) that landed inside it, not the
traffic file's lengths, which say nothing of how far the answers have
come. An entry without the function, a program without the kernel or an
engine without the event (as every program from before learned sparse
attention), or no trace gives None."""
from benchmark import architectures
from benchmark.harness import roofline
from benchmark.readers import trace_program

SPARSE_SELECT = 22


def traced_context(ctx):
    """Mean keys scored a live slot over the traced stretch, or None."""
    tr = ctx["traffic"].get("trace", {})
    seconds = ctx["seconds"]
    start = min(float(tr.get("start_s", 5.0)), seconds / 3)
    stop = start + min(float(tr.get("seconds", 4.0)), seconds / 3)
    values = [e["a"] for e in ctx["engine"]["events"]
              if e["kind"] == SPARSE_SELECT and start <= e["t"] <= stop]
    return sum(values) / len(values) if values else None


def read(ctx, program_name, kernel):
    tr = ctx["trace"]
    prog = trace_program.program(ctx, program_name)
    work_of = getattr(architectures.load(ctx["config"]), "index_kernel", None)
    context = traced_context(ctx)
    if not prog or work_of is None or context is None or not ctx["peaks"]:
        return None
    device_s = sum(s for k, s in tr["ops"].items()
                   if k.startswith(program_name + "/")
                   and kernel in k.split("/", 1)[1])
    calls = sum(n for kind, n in prog["kernel_calls"].items()
                if kernel in kind)
    a, b = ctx["engine"]["trace_open"], ctx["engine"]["trace_close"]
    d_steps = b["decode_steps"] - a["decode_steps"]
    if not device_s or not calls or not d_steps:
        return None
    batch = (b["busy_slots_acc"] - a["busy_slots_acc"]) / d_steps
    work = work_of(ctx["config"], calls, batch, context, ctx["chips"])
    return 100.0 * roofline.least_seconds(work, ctx["peaks"])["seconds"] \
        / device_s
