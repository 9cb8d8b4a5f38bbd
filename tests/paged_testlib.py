"""Paged-kernel test operands: random head-major pages and their stored form.

Cases are built head-major (``[n_pages, kvh, ps, w]`` pages,
``[n_pages, kvh, ps, 1]`` scales), which is easy to poison or permute by
physical page; :func:`run` stores them in the pool's layout through the
pool's own scatter (``repro.serve.kvq.write_pages``), gathers the scales
for the table as a step does, and calls the kernel.
"""
import jax.numpy as jnp
import numpy as np

from repro.serve import kvq


def random_pages(rng, n_pages, kvh, ps, dh, mode):
    """Random head-major K/V pages of one page mode and the mode's kernel
    operands (scales, int4 redistribution rows)."""
    shape, kw = (n_pages, kvh, ps, dh), {}
    if mode == "int8":
        kp, vp = (jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
                  for _ in range(2))
    elif mode == "int4":
        kp, vp = (kvq.pack_int4(jnp.asarray(
            rng.integers(-7, 8, shape).astype(np.int8))) for _ in range(2))
    else:
        kp, vp = (jnp.asarray(rng.normal(size=shape).astype(np.float32))
                  for _ in range(2))
    if mode != "fp":
        sdt = jnp.bfloat16 if mode == "int4" else jnp.float32
        for s in ("k_scale", "v_scale"):
            kw[s] = jnp.asarray(rng.uniform(1e-3, 2e-2, shape[:-1] + (1,))
                                .astype(np.float32)).astype(sdt)
    if mode == "int4":
        # per-head inverse redistribution rows: a few 2^e channels per head
        mask = rng.random((kvh, dh)) < 0.2
        kw["k_redist"] = jnp.asarray(kvq.redist_from_mask(mask))
        kw["v_redist"] = jnp.asarray(kvq.redist_from_mask(
            ~mask & (rng.random((kvh, dh)) < 0.2)))
    return kp, vp, kw


def scrambled_table(rng, b, pages, n_pages):
    """Distinct physical pages per slot (never the scratch page 0)."""
    perm = rng.permutation(np.arange(1, n_pages))
    return jnp.asarray(perm[:b * pages].reshape(b, pages), jnp.int32)


def _store(name, hm):
    """Head-major ``[n, kvh, ps, w]`` -> the one-layer stored array."""
    n, kvh, ps, w = hm.shape
    shape = (kvq.scale_shape(1, n, ps, kvh) if name.endswith("_scale")
             else kvq.page_shape(1, n, ps, kvh, w))
    pool = {name: jnp.zeros(shape, hm.dtype)}
    return kvq.write_pages(pool, {name: jnp.swapaxes(hm, 1, 2)}, 0,
                           jnp.arange(n)[:, None], jnp.arange(ps), ps)[name]


def stored(kp, vp, kw, table):
    """Head-major case -> (k_pages, v_pages, kernel kwargs): pages stored
    ``[1, n_pages, R, lanes]``, scales gathered for ``table`` at layer 0,
    ``page_size`` added."""
    k = _store("k", kp)
    _, kvh, ps, _ = kp.shape
    out = dict(kw, page_size=ps)
    for n in ("k_scale", "v_scale"):
        if n in kw:
            out[n] = kvq.gather_scales(_store(n, kw[n]), 0, table, kvh, ps,
                                       k.shape[2])
    return k, None if vp is None else _store("v", vp), out


def run(fn, q, kp, vp, table, pos, **kw):
    """``fn`` (the kernel or its reference) on a head-major case."""
    k, v, ops = stored(kp, vp, kw, table)
    return fn(q, k, v, table, pos, **ops)
