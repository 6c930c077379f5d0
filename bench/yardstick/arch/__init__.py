"""The architectures the yardstick knows, one module per file:
``bench/yardstick/arch/<name>.py``.

A configuration file of ``bench/configs`` names its architecture under
the key ``"yardstick"``; a file without it is ``dense``.  The harness and
the metric readers take everything that depends on the block from that
module, so an architecture joins the benchmark as a new file here beside
its configuration and traffic files.  A module provides:

- ``dims(cfg)``: the sizes it needs, from the configuration file's
  published keys; a frozen (hashable) object with at least ``n_layers``,
  ``vocab``, ``dtype`` and ``itemsize``;
- ``program_fields(mc, d)``: ``name -> (the program's value, the file's)``
  for every field of the program's ``ModelConfig`` that has to match, the
  program's ``family`` among them;
- ``params(d, key)``: the weights from the seed (:mod:`..weights`), in
  the program's parameter tree layout;
- ``served_gaps(d, seed, seqs, *, tokens, rows, q_block, targets=None)``:
  the float32 reference (:mod:`..reference`);
- ``decode_step(d, contexts, page_size)``, ``prefill_step(d, lanes,
  page_size)``, ``attn_decode``, ``attn_prefill`` and ``weight_bytes(d)``:
  the modelled ``(flops, bytes)`` of the serve path, at the lanes' real
  contexts.
"""

from __future__ import annotations

import importlib
import re

DEFAULT = "dense"


def load(name: str = DEFAULT):
    """The module ``bench/yardstick/arch/<name>.py``."""
    if not re.fullmatch(r"[A-Za-z_][A-Za-z0-9_]*", name):
        raise ValueError(f"architecture name {name!r} is not a module name")
    return importlib.import_module(f"{__name__}.{name}")


def of(cfg: dict):
    """The architecture module a configuration file names."""
    return load(cfg.get("yardstick", DEFAULT))
