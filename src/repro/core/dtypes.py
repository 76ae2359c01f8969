"""Value-storage dtypes as a first-class scheduling axis (DESIGN.md §13).

Sgap's SpMM-class workloads are memory-bandwidth-bound, so the bytes of
the CSR value stream and the gathered dense operand are a schedule knob
exactly like tile shape or reduction strategy: ``Schedule.value_dtype``
names one of :data:`VALUE_DTYPES` and every layer below (kernels,
runners, cost model, roofline) resolves it through this module.

The accumulation contract is unchanged by any choice here: kernels load
narrow and immediately ``upcast_f32`` (``kernels/common.py``), so the
dtype axis only moves *storage/traffic* precision, never reduction
precision.  ``float32`` (or ``None``) is the identity; ``int8`` selects
the quantized value path (per-row scales, ``sparse.formats.quantize_csr``)
with a ``bfloat16`` dense operand.

``float8_e4m3fn`` degrades to ``bfloat16`` with a :class:`Fp8Fallback`
warning when ``REPRO_DISABLE_FP8`` is set — schedules stay valid and
replayable; only the realized storage width changes.
"""
from __future__ import annotations

import os
import warnings

#: Valid ``Schedule.value_dtype`` names.  ``float32`` normalizes to
#: ``None`` (the default axis value) so schedule keys and cache records
#: from before the dtype axis existed stay byte-identical.
VALUE_DTYPES = ("float32", "bfloat16", "float16", "float8_e4m3fn", "int8")

#: Shorthand spellings accepted by :func:`canonical_value_dtype`.
_ALIASES = {
    "f32": "float32", "fp32": "float32",
    "bf16": "bfloat16",
    "f16": "float16", "fp16": "float16", "half": "float16",
    "fp8": "float8_e4m3fn", "f8": "float8_e4m3fn",
    "e4m3": "float8_e4m3fn", "float8": "float8_e4m3fn",
}


class Fp8Fallback(RuntimeWarning):
    """Warned when fp8 storage degrades to bf16 (missing type / env)."""


def canonical_value_dtype(value_dtype):
    """Normalize a dtype spelling to its canonical ``Schedule`` form.

    Accepts ``None``, a :data:`VALUE_DTYPES` name, a shorthand alias
    (``"bf16"``, ``"fp8"``, ...), or a numpy/jax dtype-like.  Returns
    ``None`` for float32 (the axis default) or the canonical name;
    raises ``ValueError`` for anything that is not a supported storage
    dtype.  Unsupported-on-this-jax fp8 is still *canonically valid* —
    resolution (and the bf16 fallback) happens at :func:`storage_dtype`
    time so tuned schedules remain portable across jax versions.
    """
    if value_dtype is None:
        return None
    name = value_dtype if isinstance(value_dtype, str) else None
    if name is None:
        import numpy as np

        try:
            name = np.dtype(value_dtype).name
        except TypeError as e:
            raise ValueError(f"invalid value_dtype: {value_dtype!r}") from e
    name = _ALIASES.get(name, name)
    if name not in VALUE_DTYPES:
        raise ValueError(
            f"invalid value_dtype {value_dtype!r}; expected one of "
            f"{VALUE_DTYPES} (or None)")
    return None if name == "float32" else name


def fp8_supported() -> bool:
    """True unless ``REPRO_DISABLE_FP8`` (any value but ``""``/``"0"``)
    turns ``float8_e4m3fn`` storage off — the switch the fallback tests
    use to exercise the bf16 degradation path."""
    return os.environ.get("REPRO_DISABLE_FP8", "") in ("", "0")


def storage_dtype(value_dtype):
    """Resolve a canonical value-dtype name to the jnp storage dtype.

    ``None``/``"float32"`` -> f32; ``"int8"`` -> int8 (the quantized
    value stream); fp8 -> ``jnp.float8_e4m3fn``, or ``jnp.bfloat16``
    with a :class:`Fp8Fallback` warning when :func:`fp8_supported` is
    False (degrade, never crash).
    """
    import jax.numpy as jnp

    name = canonical_value_dtype(value_dtype)
    if name is None:
        return jnp.float32
    if name == "float8_e4m3fn" and not fp8_supported():
        warnings.warn(
            "float8_e4m3fn storage disabled (REPRO_DISABLE_FP8 set); "
            "degrading value storage to bfloat16",
            Fp8Fallback, stacklevel=2)
        return jnp.bfloat16
    return getattr(jnp, name)


def operand_dtype(value_dtype):
    """Storage dtype for the *dense* operand under this value dtype.

    Narrow float values narrow the gathered operand to the same type
    (the gather stream dominates SpMM traffic).  ``int8`` values pair
    with a ``bfloat16`` operand — activation quantization is out of
    scope, but the operand still halves.  fp8 follows the same
    degradation rule as :func:`storage_dtype`.
    """
    import jax.numpy as jnp

    name = canonical_value_dtype(value_dtype)
    if name is None:
        return jnp.float32
    if name == "int8":
        return jnp.bfloat16
    return storage_dtype(name)


def value_itemsize(value_dtype) -> int:
    """Bytes per stored value under this axis choice, post-fallback.

    Used by the cost model (``core.selector.cost_terms``) and the
    roofline byte accounting; reflects the *realized* storage (a
    degraded fp8 schedule costs 2 bytes, not 1).
    """
    import numpy as np

    return int(np.dtype(storage_dtype(value_dtype)).itemsize)


def operand_itemsize(value_dtype) -> int:
    """Bytes per dense-operand element under this axis choice."""
    import numpy as np

    return int(np.dtype(operand_dtype(value_dtype)).itemsize)
