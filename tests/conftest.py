"""Shared test substrate: hermetic CPU JAX + persistent compilation cache.

Importing this before any test module pins the CPU backend unless
``JAX_PLATFORMS`` is set (so the real-hardware tier can run on a TPU host),
and places the persistent compilation cache by the repo's one rule
(``repro.launch.compile_cache``) — the suite's wall time is dominated by
recompilation, so warm runs are several times faster.  Off the TPU every
Pallas kernel runs in interpret mode (``kernels.common.interpret_mode``).
Session-scoped fixtures below hold the TableSet/symbol cases that many
tests used to rebuild per-test.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import jax

# the one place tests pin the CPU: JAX_PLATFORMS in the environment wins,
# even empty (JAX's own default backend), so the real-hardware kernel tier
# can run on a TPU host: JAX_PLATFORMS= pytest tests/test_tpu_hw.py -m tpu
if "JAX_PLATFORMS" not in os.environ:
    jax.config.update("jax_platforms", "cpu")

from repro.launch.compile_cache import setup_compile_cache  # noqa: E402

setup_compile_cache()

import jax.numpy as jnp  # noqa: E402  (after backend pinning)

from repro.core import spc  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _release_jax_executables_per_module():
    """Drop in-process jit caches at module teardown.

    The suite compiles hundreds of executables (the fused serve-decode
    scans are large); keeping every one mapped for the whole session can
    exhaust process code-mapping resources and segfault XLA's compiler
    late in the run on small CI hosts.  Compiled artifacts persist in the
    on-disk cache above, so cross-module re-compiles stay cheap — this
    only bounds *live* executables, trading a little cache-lookup time
    for a flat memory-map profile.
    """
    yield
    jax.clear_caches()


def _build_case(seed, k, lanes, t, conc):
    rng = np.random.default_rng(seed)
    tbl = spc.tables_from_probs(
        jnp.asarray(rng.dirichlet(np.full(k, conc)), jnp.float32))
    syms = rng.integers(0, k, (lanes, t))
    return tbl, syms


@pytest.fixture(scope="session")
def rans_case():
    """Memoized (TableSet, symbols) factory shared across the session.

    ``rans_case(seed, k=96, lanes=3, t=257, conc=0.4)`` — identical
    signature to the old per-module ``_random_case`` helpers, but each
    distinct case is built once per session instead of once per test.
    """
    cache: dict = {}

    def make(seed, k=96, lanes=3, t=257, conc=0.4):
        key = (seed, k, lanes, t, conc)
        if key not in cache:
            cache[key] = _build_case(seed, k, lanes, t, conc)
        return cache[key]

    return make


@pytest.fixture(scope="session")
def image_histogram_tbl():
    """Static 256-symbol TableSet from the shared image-rows histogram."""
    from repro.data.pipeline import image_rows
    counts = np.bincount(image_rows(8, 4096, seed=0).ravel(), minlength=256)
    return jax.tree.map(jnp.asarray, spc.tables_from_counts_np(counts))
