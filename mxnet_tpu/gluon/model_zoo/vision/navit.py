"""A NaViT-style vision tower: a pre-norm transformer encoder over the
patches of ONE image at its native resolution (any even x even grid of
14 x 14 patches), a 2-D rotary on queries and keys, bidirectional
attention inside the image, and a 2 x 2 patch merger that projects each
group of four patches to one row of a language model's width (the
equations are written out in ``benchmarks/references/dots_vlm.py``, the
plain reference the tests hold this file to).

* :func:`patch_positions` / :func:`patchify`: the patch order the tower
  takes (the four patches of a merge group side by side) and an
  ``H x W x 3`` image cut into it.
* :class:`NavitTower`: the parameters (the blocks' stacked along a
  leading layer axis, so that one ``lax.scan`` runs them) and an eager
  ``forward(patches, grid)``.
* :func:`navit_encode`: the pure forward over a patch-count BUCKET: the
  image's ``n_live`` patches padded to the bucket, attention bounded by
  the live count (``ops.attention.sdp_attention(kv_len=)``: on the TPU
  the flash forward skips the padding's key and query blocks), so the
  padding costs its linear share only.
* :class:`NavitEncodeEngine`: what a serving engine declares as its
  ``vision``: one program a patch-count bucket through the compiler
  service's ``serving_vision`` cache site, each named
  ``dots_vit_encode_<bucket>``, with the ``jax.named_scope``s
  ``vit.embed``, ``vit.attn``, ``vit.mlp`` and ``vit.merger``. An encode
  writes the image's rows into a request's embedding buffer in place.
"""
from __future__ import annotations

import numpy as np

from ...block import Block

__all__ = ["NavitTower", "NavitEncodeEngine", "navit_encode",
           "patch_positions", "patchify", "navit_tiny"]

PATCH = 14
_MERGER_NORM_EPS = 1e-6
_VISION_SITE = "serving_vision"


def patch_positions(rows: int, cols: int) -> np.ndarray:
    """(rows * cols, 2) int32: the (row, column) of each patch in the
    tower's order: merge group by merge group (row-major over the
    ``rows / 2 x cols / 2`` groups), a group's four patches row-major."""
    if rows % 2 or cols % 2 or rows < 2 or cols < 2:
        raise ValueError(f"a patch grid is even x even, got {rows} x {cols}")
    gi, gj, di, dj = np.meshgrid(np.arange(rows // 2), np.arange(cols // 2),
                                 (0, 1), (0, 1), indexing="ij")
    return np.stack([2 * gi + di, 2 * gj + dj], axis=-1).reshape(
        -1, 2).astype(np.int32)


def patchify(image) -> tuple:
    """An ``H x W x 3`` array (both sides multiples of 28) as
    ``(patches (N, 588), (rows, cols))`` in the tower's order; a patch is
    its 14 x 14 x 3 values, row-major."""
    image = np.asarray(image)
    if image.ndim != 3 or image.shape[2] != 3 or image.shape[0] % (2 * PATCH) \
            or image.shape[1] % (2 * PATCH) or 0 in image.shape:
        raise ValueError("an image is H x W x 3 with both sides multiples "
                         f"of {2 * PATCH}, got {image.shape}")
    rows, cols = image.shape[0] // PATCH, image.shape[1] // PATCH
    cut = image.reshape(rows, PATCH, cols, PATCH, 3).transpose(0, 2, 1, 3, 4)
    pos = patch_positions(rows, cols)
    return cut[pos[:, 0], pos[:, 1]].reshape(rows * cols, -1), (rows, cols)


def _rope_2d_tables(pos, head_dim, theta):
    """cos and sin (N, 1, head_dim / 2) of the 2-D rotary: a quarter of
    the head's dims turn with the patch's row, a quarter with its
    column; rotate-half repeats the angles over the other half."""
    import jax.numpy as jnp

    half = head_dim // 2
    inv = 1.0 / (theta ** (jnp.arange(0, half, 2, dtype=jnp.float32) / half))
    p = pos.astype(jnp.float32)
    ang = jnp.concatenate([p[:, 0:1] * inv, p[:, 1:2] * inv], axis=-1)
    return jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]


def navit_encode(w, patches, pos, n_live, *, cfg):
    """``y`` (bucket / 4, out_dim) of ONE image: ``patches`` (bucket,
    patch_dim) of which the first ``n_live`` (a traced int32 scalar) are
    the image's, ``pos`` (bucket, 2) their rows and columns. Rows of
    ``y`` at or past ``n_live / 4`` are padding."""
    import jax
    import jax.numpy as jnp

    from ....ops.attention import _rotate_pairs, rms_norm, sdp_attention
    from ....ops.nn import layer_norm
    from ..nlp.longcat_flash import _swiglu

    n = patches.shape[0]
    heads, eps = cfg["num_heads"], cfg["eps"]
    with jax.named_scope("vit.embed"):
        x = rms_norm(patches.astype(w["patch_w"].dtype) @ w["patch_w"].T
                     + w["patch_b"], w["patch_norm"], eps=eps)
        d = x.shape[1] // heads
        cos, sin = _rope_2d_tables(pos, d, cfg["rope_theta"])
    kv_len = jnp.reshape(n_live, (1,)).astype(jnp.int32)

    def rot(v):                                  # (N, H, D), rotate-half
        return _rotate_pairs(v[None], cos[None], sin[None], False)[0]

    def block(x, bw):
        with jax.named_scope("vit.attn"):
            qkv = (rms_norm(x, bw["norm1"], eps=eps) @ bw["qkv"].T).reshape(
                n, 3, heads, d)
            q, k, v = (a.transpose(1, 0, 2)[None] for a in
                       (rot(qkv[:, 0]), rot(qkv[:, 1]), qkv[:, 2]))
            att = sdp_attention(None, q, k, v, None, kv_len,
                                scale=d ** -0.5)[0]
            x = x + att.transpose(1, 0, 2).reshape(n, heads * d) \
                @ bw["proj"].T
        with jax.named_scope("vit.mlp"):
            x = x + _swiglu(rms_norm(x, bw["norm2"], eps=eps), bw["fc13"],
                            bw["fc2"])
        return x, None

    x, _ = jax.lax.scan(block, x, w["blocks"])
    with jax.named_scope("vit.merger"):
        x = layer_norm(rms_norm(x, w["post_norm"], eps=eps), w["ln_g"],
                       w["ln_b"], eps=_MERGER_NORM_EPS)
        hid = jax.nn.gelu(x.reshape(n // 4, -1) @ w["merger_a"].T
                          + w["merger_a_b"], approximate=False)
        return hid @ w["merger_b"].T + w["merger_b_b"]


class NavitTower(Block):
    """The tower's parameters and its eager forward. Defaults are the
    widths of dots.vlm1's tower (42 layers of 1,536, 12 heads of 128,
    SwiGLU 4,224, merger 6,144 -> 6,144 -> 7,168)."""

    def __init__(self, embed_dim=1536, num_layers=42, num_heads=12,
                 intermediate_size=4224, out_dim=7168, patch_dim=588,
                 eps=1e-5, rope_theta=10000.0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if embed_dim % num_heads or (embed_dim // num_heads) % 4:
            raise ValueError("the 2-D rotary needs a head size that is a "
                             "multiple of 4")
        e, f, n = embed_dim, intermediate_size, num_layers
        self.cfg = {"embed_dim": int(e), "num_layers": int(n),
                    "num_heads": int(num_heads), "intermediate_size": int(f),
                    "out_dim": int(out_dim), "patch_dim": int(patch_dim),
                    "eps": float(eps), "rope_theta": float(rope_theta)}
        shapes = {"patch_w": (e, patch_dim), "patch_b": (e,),
                  "patch_norm": (e,), "post_norm": (e,), "ln_g": (e,),
                  "ln_b": (e,), "merger_a": (4 * e, 4 * e),
                  "merger_a_b": (4 * e,), "merger_b": (out_dim, 4 * e),
                  "merger_b_b": (out_dim,)}
        blocks = {"norm1": (n, e), "qkv": (n, 3 * e, e), "proj": (n, e, e),
                  "norm2": (n, e), "fc13": (n, 2 * f, e), "fc2": (n, e, f)}
        ones = ("patch_norm", "post_norm", "ln_g", "norm1", "norm2")
        with self.name_scope():
            def get(key, shape, prefix=""):
                init = ("ones" if key in ones else
                        "zeros" if key.endswith("_b") else "xavier")
                return self.params.get(prefix + key, shape=shape, init=init)

            self.weights = {k: get(k, s) for k, s in shapes.items()}
            self.weights["blocks"] = {k: get(k, s, "blocks_")
                                      for k, s in blocks.items()}

    def arrays(self, dtype=None):
        """The weight pytree :func:`navit_encode` takes."""
        import jax
        import jax.numpy as jnp

        return jax.tree_util.tree_map(
            lambda p: p.data().data if dtype is None
            else jnp.asarray(p.data().data, dtype=dtype), self.weights)

    def forward(self, patches, grid):
        """``y`` (rows * cols / 4, out_dim) of one image, eagerly: the
        patches padded to a multiple of 128 and bounded by their count."""
        import jax.numpy as jnp

        from .... import nd

        patches = np.asarray(patches.asnumpy() if hasattr(patches, "asnumpy")
                             else patches)
        n = patches.shape[0]
        if n != int(grid[0]) * int(grid[1]):
            raise ValueError(f"{n} patches for a grid of {tuple(grid)}")
        bucket = -(-n // 128) * 128
        pos = np.zeros((bucket, 2), np.int32)
        pos[:n] = patch_positions(int(grid[0]), int(grid[1]))
        w = self.arrays()
        y = navit_encode(
            w, jnp.pad(jnp.asarray(patches, w["patch_w"].dtype),
                       ((0, bucket - n), (0, 0))),
            jnp.asarray(pos), jnp.int32(n), cfg=self.cfg)
        return nd.NDArray(data=y[:n // 4])


def _encode_into(w, patches, pos, n_live, buf, row0, *, cfg):
    """:func:`navit_encode` written into ``buf`` (1, R, out_dim) from row
    ``row0``: the image's live rows first, its padding rows behind them
    (the next image of the request overwrites those)."""
    import jax
    import jax.numpy as jnp

    y = navit_encode(w, patches, pos, n_live, cfg=cfg)
    zero = jnp.zeros((), row0.dtype)
    return jax.lax.dynamic_update_slice(
        buf, y[None].astype(buf.dtype), (zero, row0, zero))


class NavitEncodeEngine:
    """The encode stage a serving engine declares (``engine.vision``):
    ``encode`` runs ONE image through the tower at the smallest
    ``buckets`` entry that holds its patches and writes its rows into the
    request's embedding buffer (:meth:`new_buffer`: ``(1, max_rows,
    out_dim)`` on the device, in the tower's dtype). One program a
    bucket, from the compiler service's ``serving_vision`` cache site.
    Not thread-safe: the scheduler thread drives it."""

    program = "dots_vit_encode"

    def __init__(self, tower: NavitTower, buckets=(), max_rows=0):
        self.tower = tower
        self.cfg = dict(tower.cfg)
        probe = tower.weights["patch_w"].data().data
        self.dtype = str(probe.dtype)
        self._device = next(iter(probe.devices()))
        self._ident = ("navit", tuple(sorted(self.cfg.items())), self.dtype)
        self.configure(buckets, max_rows)
        self.refresh_params()

    def configure(self, buckets, max_rows) -> None:
        """``buckets``: ascending patch counts, each a multiple of 128;
        ``max_rows``: rows of a request's embedding buffer (its images'
        merged rows, the last image's up to its bucket's)."""
        self.buckets = tuple(sorted(int(b) for b in buckets))
        if any(b % 128 or b < 128 for b in self.buckets):
            raise ValueError("patch-count buckets are multiples of 128, "
                             f"got {self.buckets}")
        self.max_rows = int(max_rows)

    def refresh_params(self) -> None:
        self._params = self.tower.arrays(self.dtype)

    def bucket_of(self, n_patches: int) -> int:
        for b in self.buckets:
            if n_patches <= b:
                return b
        from ....base import MXNetError
        raise MXNetError(f"an image of {n_patches} patches is larger than "
                         f"the largest patch-count bucket {self.buckets}")

    def new_buffer(self):
        import jax
        import jax.numpy as jnp

        return jax.device_put(
            jnp.zeros((1, self.max_rows, self.cfg["out_dim"]), self.dtype,
                      device=self._device), self._device)

    def _fn(self, bucket):
        import jax

        from ....compiler import service as _csvc
        from ....compiler import signature
        from ..nlp.longcat_flash import _named

        cache = _csvc.shared_cache(_VISION_SITE)
        platform = self._device.platform
        key = signature(_VISION_SITE, self._ident,
                        avals=((bucket,), (self.max_rows,), self.dtype),
                        attrs=(), platform=platform)
        fn = cache.lookup(key)
        if fn is not cache.MISS:
            return fn
        fn = jax.jit(_named(_encode_into, f"{self.program}_{bucket}",
                            cfg=self.cfg),
                     **({"donate_argnums": (4,)} if platform != "cpu"
                        else {}))
        cache.insert(key, fn)
        return fn

    def encode(self, patches, grid, buf, row0: int):
        """Encode one image (``patches`` (N, patch_dim) host array in the
        tower's order, ``grid`` its (rows, cols)) into ``buf`` from row
        ``row0``; returns the buffer (the old one is donated) and the
        bucket it ran in. Blocks until the rows are there."""
        import jax

        from .... import telemetry
        from ....base import execution_platform

        n = int(patches.shape[0])
        bucket = self.bucket_of(n)
        if row0 + bucket // 4 > self.max_rows:
            from ....base import MXNetError
            raise MXNetError(
                f"rows {row0}..{row0 + bucket // 4} of an image's bucket "
                f"pass the {self.max_rows}-row embedding buffer")
        padded = np.zeros((bucket, patches.shape[1]), patches.dtype)
        padded[:n] = patches
        pos = np.zeros((bucket, 2), np.int32)
        pos[:n] = patch_positions(int(grid[0]), int(grid[1]))
        padded, pos, n_live, row = jax.device_put(
            (padded, pos, np.int32(n), np.int32(row0)), self._device)
        with execution_platform(self._device.platform):
            buf = self._fn(bucket)(self._params, padded, pos, n_live, buf,
                                   row)
        jax.block_until_ready(buf)
        if telemetry._state.enabled:
            telemetry.record_vision_encode(n, bucket - n)
        return buf, bucket


def navit_tiny(**kwargs):
    """Test-sized tower: 2 layers of 32, 2 heads of 16."""
    cfg = dict(embed_dim=32, num_layers=2, num_heads=2, intermediate_size=48,
               out_dim=32, patch_dim=588)
    cfg.update(kwargs)
    return NavitTower(**cfg)
