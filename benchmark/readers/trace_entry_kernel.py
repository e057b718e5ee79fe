"""One kernel family's share of its roofline in the traced window, with
the work function taken from the configuration's architecture entry BY
NAME: the least time the chip could take for the work of the calls,
inside one step program, of every kernel whose name holds `kernel`
(operations and bytes from the entry's `<work>(config, calls, batch,
chips)`, `calls` all of those calls together and `batch` the mean live
slots a decode step; peaks from benchmark/peaks.json) over the device
time those calls took, in %. An entry without the function, a program
without such a kernel (as every program from before it), or no trace
gives None."""
from benchmark import architectures
from benchmark.harness import roofline
from benchmark.readers import trace_program


def read(ctx, program_name, kernel, work):
    tr = ctx["trace"]
    prog = trace_program.program(ctx, program_name)
    work_of = getattr(architectures.load(ctx["config"]), work, None)
    if not prog or work_of is None or not ctx["peaks"]:
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
    done = work_of(ctx["config"], calls, batch, ctx["chips"])
    return 100.0 * roofline.least_seconds(done, ctx["peaks"])["seconds"] \
        / device_s
