"""Backend dispatch for the moe_gmm kernel family.

Three backends, selected explicitly via `backend=`:

  "pallas"    compiled Pallas kernel (TPU)
  "interpret" the same Pallas kernel under the interpreter (CPU-portable,
              exercises the real BlockSpec/grid machinery)
  "ref"       pure-jnp oracle

`backend=None` auto-selects: "pallas" on TPU, else "ref" ("interpret" if
`force_pallas=True`, kept for backward compatibility); "interpret" is
refused on a TPU, so a chip run never times the interpreter.  Tile-size
kwargs are honored on both Pallas backends and are accepted-but-tiling-free
on the ref path (the oracle has no tiles); unknown kwargs raise instead of
being silently swallowed."""

from __future__ import annotations

import jax

from .kernel import moe_gmm as moe_gmm_pallas
from .kernel import moe_gmm_fused as moe_gmm_fused_pallas
from .kernel import moe_gmm_fused_quant as moe_gmm_fused_quant_pallas
from .quant import (fake_quant_fp8, fit_expert_scales,
                    fit_expert_scales_from_batches, quantize_int8,
                    dequantize_int8, quantize_moe_experts)
from .ref import moe_gmm_fused_quant_ref, moe_gmm_fused_ref, moe_gmm_ref

__all__ = ["moe_gmm", "moe_gmm_pallas", "moe_gmm_ref",
           "moe_gmm_fused", "moe_gmm_fused_pallas", "moe_gmm_fused_ref",
           "moe_gmm_fused_quant", "moe_gmm_fused_quant_pallas",
           "moe_gmm_fused_quant_ref",
           "fit_expert_scales", "fit_expert_scales_from_batches",
           "quantize_int8", "dequantize_int8", "fake_quant_fp8",
           "quantize_moe_experts"]

_BACKENDS = ("pallas", "interpret", "ref")


def _resolve_backend(backend, force_pallas):
    if backend is None:
        if jax.default_backend() == "tpu":
            backend = "pallas"
        elif force_pallas:
            backend = "interpret"
        else:
            backend = "ref"
    if backend not in _BACKENDS:
        raise ValueError(f"unknown moe_gmm backend {backend!r}; "
                         f"expected one of {_BACKENDS}")
    if backend == "interpret" and jax.default_backend() == "tpu":
        raise ValueError("backend='interpret' on a TPU would run the Pallas "
                         "interpreter in place of the compiled kernel")
    return backend


def moe_gmm(x, w, counts, *, backend: str | None = None,
            force_pallas: bool = False,
            bc: int = 128, bf: int = 128, bd: int = 128):
    """Grouped expert matmul over the dense [E, C, d] dispatch buffer."""
    be = _resolve_backend(backend, force_pallas)
    if be == "ref":
        return moe_gmm_ref(x, w, counts)
    return moe_gmm_pallas(x, w, counts, bc=bc, bf=bf, bd=bd,
                          interpret=(be == "interpret"))


def moe_gmm_fused(x, wg, wu, wd, counts, *, activation: str = "swiglu",
                  backend: str | None = None, force_pallas: bool = False,
                  bc: int = 128, bf: int = 128):
    """Fused packed-union swiglu/gelu FFN over the [U_pad, C, d] layout."""
    be = _resolve_backend(backend, force_pallas)
    if be == "ref":
        return moe_gmm_fused_ref(x, wg, wu, wd, counts,
                                 activation=activation)
    return moe_gmm_fused_pallas(x, wg, wu, wd, counts,
                                activation=activation, bc=bc, bf=bf,
                                interpret=(be == "interpret"))


def moe_gmm_fused_quant(x, wg, wu, wd, s_gate, s_up, s_down, counts, *,
                        activation: str = "swiglu",
                        backend: str | None = None,
                        force_pallas: bool = False,
                        bc: int = 128, bf: int = 128):
    """Fused packed-union FFN over int8 gathered weights with per-expert
    absmax scales, dequant fused into the tiles (docs/quantization.md)."""
    be = _resolve_backend(backend, force_pallas)
    if be == "ref":
        return moe_gmm_fused_quant_ref(x, wg, wu, wd, s_gate, s_up,
                                       s_down, counts,
                                       activation=activation)
    return moe_gmm_fused_quant_pallas(x, wg, wu, wd, s_gate, s_up, s_down,
                                      counts, activation=activation,
                                      bc=bc, bf=bf,
                                      interpret=(be == "interpret"))
