"""An architecture that exists only in the tests: the tiny Llama as the
program serves it, declared as if every decode step called the step
kernel twice per layer, and, where the configuration says
`"reverse_reference_layers": true`, with a reference that is NOT the
served model's (the blocks applied in reverse order). test_architectures.py
registers it under a name and takes it through `run.run_cell` with no
edit to run.py, harness/ or readers/."""

import jax

from benchmark.architectures import llama

model_config = llama.model_config
init_params = llama.init_params
decode_step = llama.decode_step
prefill = llama.prefill
compile_shapes = llama.compile_shapes


def reference_logits(config, params, token_ids):
    if config.get("reverse_reference_layers"):
        params = dict(params, layers=jax.tree.map(lambda a: a[::-1],
                                                  params["layers"]))
    return llama.reference_logits(config, params, token_ids)


def step_kernel_calls(config):
    return 2 * llama.step_kernel_calls(config)
