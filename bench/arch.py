"""The seam between the generic harness and what is specific to one
architecture: a configuration file's ``architecture`` key names the
reference module that holds its sizes, size check, weight layout,
forward and loss, and FLOP count.

The module is found by a rule and by no table: ``ForCausalLM`` is
stripped from the name and the rest lower-cased, so ``LlamaForCausalLM``
is ``bench/reference/llama.py`` and ``DeepseekV3ForCausalLM`` would be
``bench/reference/deepseekv3.py``. A new architecture joins the
benchmark by adding that file. It provides:

  sizes(conf)                 the reference's view of the file, with
                              ``vocab_size`` and ``d_head`` (the head size
                              the fused attention streams over);
                              ``program.sizes`` adds the keys every
                              architecture shares
  check(cfg, s)               {key: (program's, file's)} of each way the
                              program's model config departs from ``s``
  layout(s)                   {leaf path: (shape, init std or "ones")}
  logits(nx, s, params, tokens, stream_rows=None), loss(nx, s, params, batch)
                              the plain reference forward and loss
  flops_per_token(s, seq)     forward plus backward model FLOPs per token
"""
from __future__ import annotations

import importlib
import re

SUFFIX = "ForCausalLM"
CONTRACT = ("sizes", "check", "layout", "logits", "loss", "flops_per_token")


def module_name(architecture: str) -> str:
    """``bench.reference.<name>`` for an architecture name."""
    if not re.fullmatch(r"[A-Za-z][A-Za-z0-9_]*", architecture):
        raise ValueError(f"architecture {architecture!r} is not a class name")
    return "bench.reference." + architecture.removesuffix(SUFFIX).lower()


def of(conf: dict):
    """The reference module of a configuration's architecture."""
    name = module_name(conf["architecture"])
    path = name.replace(".", "/") + ".py"
    try:
        mod = importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise LookupError(
            f"no reference for architecture {conf['architecture']!r}: add "
            f"{path} with {', '.join(CONTRACT)} (bench/arch.py)") from None
    missing = [f for f in CONTRACT if not callable(getattr(mod, f, None))]
    if missing:
        raise LookupError(f"{path} is no architecture's reference: it lacks "
                          f"{', '.join(missing)} (bench/arch.py)")
    return mod
