"""One kernel inside one step program, from the traced window.

what="share": the kernel's device time over the chip's busy time, in %.
what="roofline": the least time the chip could take for the work of the
kernel's calls in the trace (operations and bytes from a function of the
configuration's architecture entry, `attention_kernel(config, calls,
batch, context, chips)`, kept beside its `decode_step`; peaks from
benchmark/peaks.json) over the device time those calls took, in %. An
entry without the function, or a trace without the kernel, gives None.
"""
from benchmark import architectures
from benchmark.harness import roofline
from benchmark.readers import trace_program, trace_roofline


def read(ctx, program_name, kernel, what):
    tr = ctx["trace"]
    prog = trace_program.program(ctx, program_name)
    if not prog or not tr["busy_s"]:
        return None
    device_s = sum(s for k, s in tr["ops"].items()
                   if k.startswith(program_name + "/")
                   and kernel in k.split("/", 1)[1])
    if not device_s:
        return None
    if what == "share":
        return 100.0 * device_s / tr["busy_s"]
    if what != "roofline":
        raise ValueError(f"what={what!r}")
    work_of = getattr(architectures.load(ctx["config"]), "attention_kernel",
                      None)
    a, b = ctx["engine"]["trace_open"], ctx["engine"]["trace_close"]
    d_steps = b["decode_steps"] - a["decode_steps"]
    if work_of is None or not d_steps or not ctx["peaks"]:
        return None
    calls = sum(n for kind, n in prog["kernel_calls"].items()
                if kernel in kind)
    batch = (b["busy_slots_acc"] - a["busy_slots_acc"]) / d_steps
    mean_p, _, mean_o = trace_roofline._lengths(ctx)
    work = work_of(ctx["config"], calls, batch, mean_p + mean_o / 2,
                   ctx["chips"])
    return 100.0 * roofline.least_seconds(work, ctx["peaks"])["seconds"] \
        / device_s
