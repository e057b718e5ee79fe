"""The window rows' attention kernel's share of its roofline in the
traced stretch: the least time the chip could take for the pages the
kernel's calls WALKED (bytes and operations from the configuration's
architecture entry, `window_attention_pages(config, pages, calls, batch,
chips)`; peaks from benchmark/peaks.json) over the device time those
calls took, in %. The PAGES are what ran: the engine's `window_cache`
flight events (kind 23) that landed inside the traced stretch carry, in
`aux`, the pages their block's window calls walked and the calls it made
(`window_pages=<n> calls=<m>`); their pages a call, times the calls the
trace holds, is the work. Nothing comes from the traffic file's lengths,
which say nothing of how far the answers have come. An entry without the
function, a program without the kernel, an engine without the event (as
every program from before window rows) or no trace gives None."""
from benchmark import architectures
from benchmark.harness import roofline
from benchmark.readers import trace_program

WINDOW_CACHE = 23


def traced_pages_per_call(ctx):
    """Pages a window call walked over the traced stretch, or None."""
    tr = ctx["traffic"].get("trace", {})
    seconds = ctx["seconds"]
    start = min(float(tr.get("start_s", 5.0)), seconds / 3)
    stop = start + min(float(tr.get("seconds", 4.0)), seconds / 3)
    pages = calls = 0
    for e in ctx["engine"]["events"]:
        if e["kind"] == WINDOW_CACHE and start <= e["t"] <= stop:
            aux = dict(kv.split("=", 1) for kv in e["aux"].split())
            pages += int(aux["window_pages"])
            calls += int(aux["calls"])
    return pages / calls if calls else None


def read(ctx, program_name, kernel):
    tr = ctx["trace"]
    prog = trace_program.program(ctx, program_name)
    work_of = getattr(architectures.load(ctx["config"]),
                      "window_attention_pages", None)
    if not prog or work_of is None or not ctx["peaks"]:
        return None
    per_call = traced_pages_per_call(ctx)
    device_s = sum(s for k, s in tr["ops"].items()
                   if k.startswith(program_name + "/")
                   and kernel in k.split("/", 1)[1])
    calls = sum(n for kind, n in prog["kernel_calls"].items()
                if kernel in kind)
    a, b = ctx["engine"]["trace_open"], ctx["engine"]["trace_close"]
    d_steps = b["decode_steps"] - a["decode_steps"]
    if per_call is None or not device_s or not calls or not d_steps:
        return None
    batch = (b["busy_slots_acc"] - a["busy_slots_acc"]) / d_steps
    work = work_of(ctx["config"], per_call * calls, calls, batch,
                   ctx["chips"])
    return 100.0 * roofline.least_seconds(work, ctx["peaks"])["seconds"] \
        / device_s
