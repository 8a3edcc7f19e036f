"""jit'd public wrappers for the walk-step kernels (view-pair layout).

``node2vec_step`` is the single-hop form of the fused advance: with
``use_kernel=True`` it runs :func:`repro.kernels.pair_advance
.fused_advance_pair` capped at one hop (``max_hops=1``, termination
disabled); with ``use_kernel=False`` it draws the same counter-keyed
uniforms through :mod:`repro.kernels.rng` on the host and feeds the
independent dense oracle :func:`repro.kernels.node2vec_ref
.node2vec_step_ref`.  The two paths agree bit for bit — that equality is
what validates the kernel's internal RNG and sampling logic, and tests
sweep both.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import rng
from .node2vec_ref import node2vec_step_ref
from .pair_advance import WALK_TILE, fused_advance_pair

__all__ = ["node2vec_step", "alias_step"]


@partial(
    jax.jit,
    static_argnames=(
        "p",
        "q",
        "order",
        "k_max",
        "n_iters",
        "v_iters",
        "has_alias",
        "use_kernel",
        "interpret",
        "walk_tile",
    ),
)
def node2vec_step(
    vids,
    nverts,
    vid_base,
    indptr,
    ptr_base,
    indices,
    ind_base,
    alias_j,
    alias_q,
    wid,
    prev,
    cur,
    hop,
    active,
    key,
    *,
    p: float = 1.0,
    q: float = 1.0,
    order: int = 2,
    k_max: int = 4,
    n_iters: int = 24,
    v_iters: int = 12,
    has_alias: bool = False,
    use_kernel: bool = True,
    interpret: bool = True,
    walk_tile: int = WALK_TILE,
):
    """One walk hop for a batch over a resident pair. Returns (z, moved)."""
    if use_kernel:
        _, cur_f, hop_f, _, _, _, _ = fused_advance_pair(
            vids,
            nverts,
            vid_base,
            indptr,
            ptr_base,
            indices,
            ind_base,
            alias_j,
            alias_q,
            wid,
            prev,
            cur,
            hop,
            active,
            key,
            jnp.int32(jnp.iinfo(jnp.int32).max),  # never length-finished
            jnp.float32(1.0),  # never decay-stopped
            jnp.float32(p),
            jnp.float32(q),
            order=order,
            k_max=k_max,
            n_iters=n_iters,
            v_iters=v_iters,
            record=False,
            has_alias=has_alias,
            max_len=1,
            max_hops=1,
            interpret=interpret,
            walk_tile=walk_tile,
        )
        return cur_f, hop_f - hop
    # reference path: materialize the counter-keyed draws explicitly —
    # (base_key, walk_id, hop, round), exactly the kernel's fold chain
    kw0, kw1 = rng.fold_in(*rng.fold_in(*rng.key_halves(key), wid), hop)
    unif = jnp.stack(
        [jnp.stack(rng.uniform3(*rng.fold_in(kw0, kw1, kk)), axis=-1) for kk in range(k_max)],
        axis=1,
    )
    return node2vec_step_ref(
        vids,
        nverts,
        vid_base,
        indptr,
        ptr_base,
        indices,
        ind_base,
        alias_j,
        alias_q,
        prev,
        cur,
        hop,
        active,
        unif,
        p=p,
        q=q,
        order=order,
        k_max=k_max,
        has_alias=has_alias,
    )


@partial(
    jax.jit,
    static_argnames=("v_iters", "has_alias", "use_kernel", "interpret", "walk_tile"),
)
def alias_step(
    vids,
    nverts,
    vid_base,
    indptr,
    ptr_base,
    indices,
    ind_base,
    alias_j,
    alias_q,
    wid,
    cur,
    active,
    key,
    *,
    v_iters: int = 12,
    has_alias: bool = True,
    use_kernel: bool = True,
    interpret: bool = True,
    walk_tile: int = WALK_TILE,
):
    """First-order (DeepWalk) hop: alias/uniform neighbor draw."""
    zero = jnp.zeros_like(cur)
    return node2vec_step(
        vids,
        nverts,
        vid_base,
        indptr,
        ptr_base,
        indices,
        ind_base,
        alias_j,
        alias_q,
        wid,
        zero,
        cur,
        zero,
        active,
        key,
        p=1.0,
        q=1.0,
        order=1,
        k_max=1,
        n_iters=1,
        v_iters=v_iters,
        has_alias=has_alias,
        use_kernel=use_kernel,
        interpret=interpret,
        walk_tile=walk_tile,
    )
