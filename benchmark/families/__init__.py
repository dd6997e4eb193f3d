"""A configuration's model family, found by name like a per-layer reader or a
traffic kind: the configuration file's optional key ``"family"`` (absent:
``"llama"``) names ``benchmark/families/<family>.py``. A family says how the
program is told about a configuration and how logits are produced on each side
of the check; the harness (``harness/correct.py``) draws the prompts, holds
the tolerance and gives the verdict. The contract, and all of it:

``model_config(name, config) -> object``
    The program's model configuration from the configuration file's published
    keys, plus the keys of a cut (layers, experts held here, a vocabulary
    slice). ``harness/gateway.register_model`` puts what it returns under
    ``MODEL_CONFIGS[name]`` before ``build_app``.

``engine_logits(engine, check) -> callable(prompt, forced) -> float32 [1 + len(forced), V]``
    Logits of the last prompt position and of one decode step per forced
    token, from the program's own prefill and decode functions through its own
    cache layout, mesh and kernel choice, at the check's lengths
    (``check.prompt_lengths``, ``check.decode_positions``). Where the engine
    would send a prompt of that length through another path than a short one
    (in chunks through a history path above its prefill bucket), that is the
    path to drive, since it is the one the cells time; a family that cannot
    yet, refuses such lengths (``llama.py`` does). The callable may carry
    ``impl``, the kernel choices it traced with, which the check prints.

``reference``
    The name of a module ``benchmark/reference/<reference>.py`` whose
    ``forward(params, model_config, tokens, positions)`` returns ``(logits
    [len(positions), V], routing margins or None)``. Every reference keeps
    ``reference/decoder.py``'s rule: float32 under
    ``default_matmul_precision("highest")``; no cache, kernel or batching;
    nothing of the program's model code imported; the engine's own weight tree,
    dequantised a layer at a time.

A family never decides whether the two sides agree. No file there, or a file
that lacks one of the three names, is refused with the path looked at
(``harness/manifest.load_by_name``, which finds readers the same way), before
any device work. A later PR brings a family as new files
(``families/<family>.py``, ``reference/<reference>.py``, cost functions beside
``harness/kernel_cost.py``, readers for its own kernels) and edits none that
is here.
"""

from __future__ import annotations

import os
from types import ModuleType

from ..harness.manifest import BENCH_DIR, load_by_name

DEFAULT = "llama"
FAMILY_DIR = os.path.join(BENCH_DIR, "families")
REFERENCE_DIR = os.path.join(BENCH_DIR, "reference")
CONTRACT = ("model_config", "engine_logits", "reference")


def load(name: str | None = None) -> ModuleType:
    """The family ``name`` (None: the default), held to the contract's three names."""
    return load_by_name(FAMILY_DIR, DEFAULT if name is None else name, "family", CONTRACT)


def of(config: dict) -> ModuleType:
    """The family of a configuration file's contents."""
    return load(config.get("family"))


def reference_of(family: ModuleType) -> ModuleType:
    """The plain reference a family names, with its ``forward``."""
    return load_by_name(REFERENCE_DIR, family.reference, "reference", ("forward",))
