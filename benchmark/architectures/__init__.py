"""Everything the harness knows about a model's architecture, one module
per architecture: benchmark/architectures/<name>.py, found by the name a
configuration file gives under "architecture" (absent means "llama").
Encoder towers beside a decoder are found the same way under
benchmark/architectures/encoders/. See benchmark/README.md for the six
items an entry supplies and their signatures.

Only the entries import `generativeaiexamples_tpu.models` or read a
configuration file's published shape keys; run.py, harness/ and readers/
go through `load`.
"""

from __future__ import annotations

import importlib
import pkgutil
from typing import Any, Dict, List

DEFAULT = "llama"
DEFAULT_ENCODER = "bert"

# what an entry supplies (benchmark/README.md, "An architecture")
ITEMS = ("model_config", "init_params", "reference_logits", "decode_step",
         "prefill", "step_kernel_calls", "compile_shapes")


def known(package: str = __name__) -> List[str]:
    """The names that resolve: the package's modules."""
    path = importlib.import_module(package).__path__
    return sorted(m.name for m in pkgutil.iter_modules(path) if not m.ispkg)


def _module(package: str, name: str):
    full = f"{package}.{name}"
    if name.isidentifier():
        try:
            return importlib.import_module(full)
        except ModuleNotFoundError as e:
            if e.name != full:  # the entry exists; what it imports does not
                raise
    raise ValueError(f"unknown architecture {name!r} (known: "
                     f"{known(package)}); add {package.replace('.', '/')}"
                     f"/{name}.py")


def load(config: Dict[str, Any]):
    """The entry of a configuration's decoder. An unknown name is an
    error, never a default."""
    entry = _module(__name__, str(config.get("architecture", DEFAULT)))
    missing = [i for i in ITEMS if not callable(getattr(entry, i, None))]
    if missing:
        raise TypeError(f"{entry.__name__} lacks {missing}: an entry "
                        f"supplies all of {list(ITEMS)}")
    return entry


def load_encoder(spec: Dict[str, Any]):
    """The entry of one tower under a configuration's `encoders`."""
    return _module(__name__ + ".encoders",
                   str(spec.get("architecture", DEFAULT_ENCODER)))
