"""Public jit'd wrapper over the SSD Pallas kernel.

``ssd_chunk_scan`` dispatches between the Pallas kernel (TPU target;
interpret mode on CPU for validation) and the pure-jnp oracle in ``ref.py``.
The default backend policy: on TPU run the compiled kernel, anywhere else run
the oracle — so models can call it unconditionally and dry-runs lower the jnp
path.

``impl`` overrides: "pallas" (compiled), "interpret" (kernel body on CPU),
"ref" (oracle).
"""

from __future__ import annotations

import jax

from repro.kernels import ref as _ref
from repro.kernels.ssd_scan import ssd_chunk_scan_pallas

__all__ = ["ssd_chunk_scan"]


def _auto_impl(impl: str | None) -> str:
    if impl is not None:
        return impl
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def ssd_chunk_scan(x, a_log, b, c, h0=None, chunk: int = 128,
                   impl: str | None = None, **kw):
    impl = _auto_impl(impl)
    if impl == "ref":
        return _ref.ssd_chunk_scan_ref(x, a_log, b, c, chunk, h0)
    return ssd_chunk_scan_pallas(x, a_log, b, c, h0, chunk=chunk,
                                 interpret=(impl == "interpret"), **kw)
