"""Architecture modules: everything of the benchmark that is particular
to one model architecture, one module each, named as the configuration
file's `reference` (`bench/reference/<name>.py`) is.

A module provides:

- `Arch`, a frozen dataclass of the sizes and semantics a configuration
  file states, with `vocab`, `dtype` and `dtype_bytes`;
- `arch_of(conf)`, the `Arch` of a configuration file;
- `program_config(conf)`, the program's `ModelConfig` for the file,
  raising ValueError where it would run other widths or semantics;
- `program_params(arch, seed)`, the program's parameter tree from the
  seed, made on the device in the served type, with the very values the
  reference regenerates;
- the counts `bench.flops` dispatches to: `attn_params`,
  `expert_params`, `dense_params`, `flops_per_token`,
  `kv_bytes_per_position`, `pass_bytes` and `scope_counts`.

An architecture is added as a new module here and a reference beside it;
nothing else in `bench/` names one."""

from __future__ import annotations

import importlib
import sys


def for_config(conf: dict):
    """The architecture module a configuration file selects:
    `bench.archs.<reference>`."""
    return importlib.import_module(f"{__name__}.{conf['reference']}")


def of(arch):
    """The architecture module that defines `arch`'s type."""
    return sys.modules[type(arch).__module__]
