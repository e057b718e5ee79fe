"""Device time of one jitted step program in the traced window, per unit
of its work.

per="step": per decode step. Steps are counted in the trace itself: the
step kernel's calls inside the program over the calls one step makes,
which the configuration's architecture entry states (for the Llama block
the paged-attention kernel runs once per layer per step).
per="ktok": per thousand prompt tokens prefilled while the trace ran
(the engine's `prefill_tokens` counter read at both ends of the trace).
"""
from benchmark import architectures


def program(ctx, name):
    tr = ctx["trace"]
    if not tr:
        return None
    return tr["programs"].get(name)


def decode_steps(ctx, prog, step_kernel):
    calls = sum(n for kind, n in prog["kernel_calls"].items()
                if step_kernel in kind)
    per_step = architectures.load(ctx["config"]).step_kernel_calls(
        ctx["config"])
    return calls / per_step if calls else None


def read(ctx, program_name, per, step_kernel="paged_attention"):
    prog = program(ctx, program_name)
    if not prog or not prog["device_s"]:
        return None
    if per == "step":
        steps = decode_steps(ctx, prog, step_kernel)
        return prog["device_s"] / steps * 1e3 if steps else None
    if per == "ktok":
        e = ctx["engine"]
        toks = e["trace_close"]["prefill_tokens"] \
            - e["trace_open"]["prefill_tokens"]
        return prog["device_s"] / (toks / 1e3) * 1e3 if toks > 0 else None
    raise ValueError(f"per={per!r}")
