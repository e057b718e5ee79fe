"""A step program's share of its roofline: the least time the chip could
take for the work it did in the traced window (operations and bytes from
the configuration's architecture entry, peaks from benchmark/peaks.json)
over the device time it took. The work is the algorithm's: padding and
re-reads lower the share."""
from benchmark import architectures
from benchmark.harness import roofline, traffic
from benchmark.readers import trace_program


def _lengths(ctx):
    spec = ctx["traffic"]
    n = 256
    p = traffic.stratified(spec["prompt_tokens"], n)
    o = traffic.stratified(spec["output_tokens"], n)
    served = spec.get("served_prompt_tokens_mean")
    mean_p = float(served) if served else sum(p) / n
    weighted_p = float(served) if served else sum(x * x for x in p) / sum(p)
    return mean_p, weighted_p, sum(o) / n


def read(ctx, program_name, phase, step_kernel="paged_attention"):
    prog = trace_program.program(ctx, program_name)
    if not prog or not prog["device_s"] or not ctx["peaks"]:
        return None
    arch = architectures.load(ctx["config"])
    e = ctx["engine"]
    a, b = e["trace_open"], e["trace_close"]
    mean_p, weighted_p, mean_o = _lengths(ctx)
    if phase == "decode":
        steps = trace_program.decode_steps(ctx, prog, step_kernel)
        d_steps = b["decode_steps"] - a["decode_steps"]
        if not steps or not d_steps:
            return None
        batch = (b["busy_slots_acc"] - a["busy_slots_acc"]) / d_steps
        work = arch.decode_step(ctx["config"], batch, mean_p + mean_o / 2,
                                ctx["chips"])
        work = {k: v * steps for k, v in work.items()}
    elif phase == "prefill":
        toks = b["prefill_tokens"] - a["prefill_tokens"]
        if toks <= 0:
            return None
        work = arch.prefill(ctx["config"], toks, weighted_p,
                            prog["executions"], ctx["chips"])
    else:
        raise ValueError(f"phase={phase!r}")
    least = roofline.least_seconds(work, ctx["peaks"])["seconds"]
    return 100.0 * least / prog["device_s"]
