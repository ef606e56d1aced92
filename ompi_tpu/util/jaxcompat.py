"""The single import site for the jax surfaces that have moved between
releases.

The tree is built and checked against ONE installation — jax 0.9.0 /
jaxlib 0.9.0 (the sandbox, the chip machine and the CI lane all carry
it) — so nothing here branches on a version: each helper names where
that release keeps the surface. When the installation moves, this is
the only module to edit (ROADMAP ground rule: version drift lives
here, never at call sites).
"""

from __future__ import annotations


def axis_size(axis) -> int:
    """Static size of a named mesh axis inside an SPMD region (a plain
    int, safe in shape arithmetic)."""
    from jax import lax

    return lax.axis_size(axis)


def shard_map(fn, *, mesh, in_specs, out_specs, **kw):
    """``jax.shard_map`` (varying-axes check spelled ``check_vma=``)."""
    import jax

    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def pallas():
    """The Pallas core module (``pl``)."""
    from jax.experimental import pallas as pl

    return pl


def pallas_tpu():
    """The Pallas TPU extension module (``pltpu``: remote-DMA copies,
    DMA/barrier semaphores, TPU memory spaces)."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu


def pallas_compiler_params(collective_id: int):
    """Mosaic compiler params for a kernel that DMAs to other chips:
    the barrier semaphore needs a ``collective_id``, and the remote
    copies are side effects the compiler must not eliminate."""
    return pallas_tpu().CompilerParams(
        has_side_effects=True, collective_id=collective_id)


def pallas_device_id_type():
    """Mesh-coordinate addressing for ``make_async_remote_copy`` /
    ``semaphore_signal`` (``device_id`` is a tuple of ``shard_map``
    axis indices)."""
    return pallas_tpu().DeviceIdType.MESH


#: wire-format name -> (ml_dtypes attribute, bytes/element) for the
#: compressed-DCN transports.
_WIRE_SPECS = (
    ("bf16", "bfloat16", 2),
    ("fp8_e4m3", "float8_e4m3fn", 1),
    ("fp8_e5m2", "float8_e5m2", 1),
)

_wire_cache: dict = {}


def _wire_table() -> dict:
    """name -> numpy dtype of every wire format, built once."""
    table = _wire_cache.get("table")
    if table is None:
        import ml_dtypes
        import numpy as np

        table = {name: np.dtype(getattr(ml_dtypes, attr))
                 for name, attr, _isz in _WIRE_SPECS}
        _wire_cache["table"] = table
    return table


def wire_dtype(name: str):
    """numpy dtype for a compressed-DCN wire-format name ('bf16',
    'fp8_e4m3', 'fp8_e5m2'), or None for any other name."""
    return _wire_table().get(name)


def wire_itemsize(name: str) -> int:
    """Bytes per element of a wire format (0 for unknown names) —
    static, no capability probe, safe for pure byte accounting."""
    for n, _attr, isz in _WIRE_SPECS:
        if n == name:
            return isz
    return 0


def wire_finfo_max(name: str) -> float:
    """Largest finite value of a wire format (the fp8 scale-factor
    denominator). ``ml_dtypes.finfo``, not ``np.finfo`` — numpy's
    rejects the extended dtypes it did not define."""
    import ml_dtypes

    return float(ml_dtypes.finfo(_wire_table()[name]).max)


def np_dtype(name: str):
    """``np.dtype`` over the ml_dtypes-extended namespace: 'bfloat16'
    and the float8 spellings resolve like builtins (importing
    ml_dtypes registers them with numpy)."""
    import ml_dtypes  # noqa: F401 — import registers extended dtypes
    import numpy as np

    return np.dtype(name)


def pallas_remote_dma_ok() -> bool:
    """Whether ``make_async_remote_copy`` kernels can run on the
    current default backend: only a real TPU moves data between chips
    by DMA. Elsewhere :mod:`ompi_tpu.coll.pallas_kernels` and
    :mod:`ompi_tpu.osc.pallas` run the same schedule as interpret-mode
    compute kernels with ``ppermute`` hops."""
    import jax

    try:
        return jax.default_backend() == "tpu"
    except RuntimeError:
        return False
