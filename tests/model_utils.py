"""Shared helpers: build random synthetic models, write them as `.m` files.

The implementation lives in ``distributed_llama_tpu.formats.synthetic`` (the
load generator's self-hosted server uses the same writer — one copy of the
layout/init rules); this module keeps the historical test-suite import path.
"""

from __future__ import annotations

from distributed_llama_tpu.formats.synthetic import (  # noqa: F401  (re-export)
    random_tensors,
    tiny_spec,
    write_model_file,
)
