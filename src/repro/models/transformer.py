"""Unified LM: dense / MoE / MLA / SSM / hybrid decoder-only models.

One ``ModelConfig`` describes every assigned architecture; ``init_lm`` builds
a stacked-params pytree (+ logical axes twin), ``forward`` is the train /
prefill path (scan over layers, optional remat), ``decode_step`` the serving
path with KV / SSM-state caches.

Approximate Random Dropout is a first-class argument: every entry point
takes a pattern — a ``core.plan.BoundPlan`` (static dp/bias bound from a
``DropoutPlan``), or the legacy ``PatternArgs`` shim — and the FFN/MoE/SSM
blocks compute only the kept 1/dp of their hidden units (see layers.py).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import plan as plan_mod
from repro.parallel.sharding import constrain
from . import layers as L
from .layers import NO_PATTERN, PatternArgs  # noqa: F401 (re-export compat)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    # attention
    qkv_bias: bool = False
    rope_theta: float = 1e6
    sliding_window: Optional[int] = None
    global_every: int = 0          # gemma3: layer i is global iff (i+1) % k == 0
    # moe
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    n_shared: int = 0
    n_dense_layers: int = 0        # deepseek: first k layers dense
    capacity_factor: float = 1.25
    # mla
    mla: bool = False
    q_lora: int = 0
    kv_lora: int = 0
    qk_nope: int = 0
    qk_rope: int = 0
    v_head_dim: int = 0
    mla_absorb: bool = True        # absorbed decode matmuls (perf)
    mtp: bool = False
    moe_impl: str = "scatter"      # scatter | ep_shardmap (optimized EP)
    # ssm
    ssm_state: int = 0
    ssm_headdim: int = 64
    ssm_expand: int = 2
    d_conv: int = 4
    hybrid_period: int = 6         # zamba2: shared attn block every k-th slot
    # modality frontends (stubs per assignment)
    n_codebooks: int = 0           # musicgen
    vision_tokens: int = 0         # internvl
    vision_dim: int = 0
    # io
    tie_embeddings: bool = False
    # approximate random dropout
    dropout_rate: float = 0.0
    pattern_kind: str = "rdp"
    pattern_nb: int = 128          # pattern blocks over d_ff (dp must divide;
                                   # d_ff / pattern_nb a multiple of 128 for
                                   # the Pallas kernels on TPU)
    # numerics / perf
    dtype: str = "bfloat16"
    norm_eps: float = 1e-6
    attn_chunk: int = 1024
    ssd_chunk: int = 256
    remat: bool = True
    remat_policy: str = "full"     # full | dots (save dot outputs — bwd
                                   # skips recomputing matmuls AND their
                                   # partial-sum collectives)
    logit_softcap: float = 0.0

    @property
    def jdtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32

    def layer_kind(self, i: int) -> str:
        if self.family == "ssm":
            return "ssm"
        if self.family == "hybrid":
            return "attn_shared" if i % self.hybrid_period == self.hybrid_period - 1 else "ssm"
        if self.family == "moe":
            return "dense" if i < self.n_dense_layers else "moe"
        return "dense"

    def is_global_layer(self, i: int) -> bool:
        if self.global_every <= 0:
            return True
        return (i + 1) % self.global_every == 0

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_headdim


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _stack_axes(axes_tree, n: int):
    return jax.tree.map(
        lambda ax: (None,) + ax,
        axes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))


def _dense_layer(cfg: ModelConfig):
    dt = cfg.jdtype
    if cfg.mla:
        attn_p, attn_a = L.init_mla(cfg.d_model, cfg.n_heads, cfg.q_lora,
                                    cfg.kv_lora, cfg.qk_nope, cfg.qk_rope,
                                    cfg.v_head_dim, dt)
    else:
        attn_p, attn_a = L.init_attention(cfg.d_model, cfg.n_heads,
                                          cfg.n_kv_heads, cfg.head_dim,
                                          cfg.qkv_bias, dt)
    ffn_p, ffn_a = L.init_ffn(cfg.d_model, cfg.d_ff, gated=True, dtype=dt)
    n1, a1 = L.init_rmsnorm(cfg.d_model)
    n2, a2 = L.init_rmsnorm(cfg.d_model)
    return ({"attn": attn_p, "ffn": ffn_p, "norm1": n1, "norm2": n2},
            {"attn": attn_a, "ffn": ffn_a, "norm1": a1, "norm2": a2})


def _moe_layer(cfg: ModelConfig):
    dt = cfg.jdtype
    if cfg.mla:
        attn_p, attn_a = L.init_mla(cfg.d_model, cfg.n_heads, cfg.q_lora,
                                    cfg.kv_lora, cfg.qk_nope, cfg.qk_rope,
                                    cfg.v_head_dim, dt)
    else:
        attn_p, attn_a = L.init_attention(cfg.d_model, cfg.n_heads,
                                          cfg.n_kv_heads, cfg.head_dim,
                                          cfg.qkv_bias, dt)
    moe_p, moe_a = L.init_moe(cfg.d_model, cfg.moe_d_ff, cfg.n_experts,
                              cfg.n_shared, dt)
    n1, a1 = L.init_rmsnorm(cfg.d_model)
    n2, a2 = L.init_rmsnorm(cfg.d_model)
    return ({"attn": attn_p, "moe": moe_p, "norm1": n1, "norm2": n2},
            {"attn": attn_a, "moe": moe_a, "norm1": a1, "norm2": a2})


def _ssm_layer(cfg: ModelConfig):
    p, a = L.init_mamba2(cfg.d_model, cfg.ssm_state, cfg.ssm_headdim,
                         cfg.ssm_expand, cfg.d_conv, cfg.jdtype)
    n, na = L.init_rmsnorm(cfg.d_model)
    return {"ssm": p, "norm1": n}, {"ssm": a, "norm1": na}


def _shared_attn_block(cfg: ModelConfig):
    """Zamba2-style shared block: concat(h, x0) → attn → FFN (own weights,
    reused at every application site)."""
    dt = cfg.jdtype
    d2 = 2 * cfg.d_model
    hd = d2 // cfg.n_heads
    attn_p, attn_a = L.init_attention(d2, cfg.n_heads, cfg.n_kv_heads, hd, False, dt)
    # o-proj must land back in d_model
    attn_p["wo"] = jnp.zeros((cfg.n_heads, hd, cfg.d_model), dt)
    ffn_p, ffn_a = L.init_ffn(cfg.d_model, cfg.d_ff, gated=True, dtype=dt)
    n1 = {"scale": jnp.ones((d2,), jnp.float32)}
    n2, a2 = L.init_rmsnorm(cfg.d_model)
    return ({"attn": attn_p, "ffn": ffn_p, "norm1": n1, "norm2": n2},
            {"attn": attn_a, "ffn": ffn_a, "norm1": {"scale": ("embed",)},
             "norm2": a2})


def layer_groups(cfg: ModelConfig) -> list[tuple[str, int]]:
    """Contiguous (kind, count) runs over layers — each run is one scan."""
    runs, prev, cnt = [], None, 0
    for i in range(cfg.n_layers):
        k = cfg.layer_kind(i)
        if k == prev:
            cnt += 1
        else:
            if prev is not None:
                runs.append((prev, cnt))
            prev, cnt = k, 1
    runs.append((prev, cnt))
    return runs


def init_lm(cfg: ModelConfig):
    """Returns (abstract_params, axes).  Use layers.materialize for weights."""
    dt = cfg.jdtype
    params, axes = {}, {}
    if cfg.n_codebooks:
        params["embed"] = {"tok": jnp.zeros((cfg.n_codebooks, cfg.vocab,
                                             cfg.d_model), dt)}
        axes["embed"] = {"tok": (None, "vocab", "embed")}
        params["heads"] = jnp.zeros((cfg.n_codebooks, cfg.d_model, cfg.vocab), dt)
        axes["heads"] = (None, "embed", "vocab")
    else:
        params["embed"], axes["embed"] = L.init_embed(
            cfg.vocab, cfg.d_model, cfg.tie_embeddings, dt)
    if cfg.vision_tokens:
        params["vision_proj"] = {
            "norm": {"scale": jnp.ones((cfg.vision_dim,), jnp.float32)},
            "w1": jnp.zeros((cfg.vision_dim, cfg.d_model), dt),
            "w2": jnp.zeros((cfg.d_model, cfg.d_model), dt)}
        axes["vision_proj"] = {"norm": {"scale": (None,)},
                               "w1": (None, "embed"), "w2": ("embed", "embed")}

    # layer stacks (one per contiguous kind-run)
    stacks, stack_axes = [], []
    maker = {"dense": _dense_layer, "moe": _moe_layer, "ssm": _ssm_layer}
    for kind, count in layer_groups(cfg):
        if kind == "attn_shared":
            continue  # shared weights live outside the stacks
        ps, as_ = zip(*(maker[kind](cfg) for _ in range(count)))
        stacks.append(_stack(list(ps)))
        stack_axes.append(_stack_axes(as_[0], count))
    params["stacks"] = stacks
    axes["stacks"] = stack_axes
    if cfg.family == "hybrid":
        params["shared_attn"], axes["shared_attn"] = _shared_attn_block(cfg)

    params["final_norm"], axes["final_norm"] = L.init_rmsnorm(cfg.d_model)
    if cfg.mtp:
        mtp_cfg = dataclasses.replace(cfg, mla=cfg.mla, mtp=False)
        lp, la = _dense_layer(mtp_cfg)
        params["mtp"] = {"proj": jnp.zeros((2 * cfg.d_model, cfg.d_model), dt),
                         "layer": lp}
        axes["mtp"] = {"proj": (None, "embed"), "layer": la}
    return params, axes


def batch_logical_axes(cfg: ModelConfig, batch) -> dict:
    """Logical-axes twin of a training batch pytree.

    The batch layout is model-defined (codebook archs carry [B, K, S]
    token/label tensors, vision archs add an embeddings leaf), so the axes
    mapping lives here next to ``forward``.  ``DistributedTrainer`` turns
    this into explicit input shardings: the leading dim shards over the
    batch mesh axes, everything else is replicated."""
    def ax(path, x):
        name = str(getattr(path[-1], "key", path[-1]))
        if name == "vision_embeds":                  # [B, T_v, d_vision]
            return ("batch", "seq", None)
        if cfg.n_codebooks and x.ndim == 3:          # [B, K, S]
            return ("batch", None, "seq")
        return ("batch",) + ("seq",) * (x.ndim - 1)  # [B, S] tokens/labels
    return jax.tree_util.tree_map_with_path(ax, batch)


# --------------------------------------------------------------------------
# forward (train / prefill trunk)
# --------------------------------------------------------------------------

def _attn(cfg, lp, x, pat, layer_idx, window):
    """The attention sub-layer and its pre-norm, under the ``attn`` scope
    (a device trace puts the time of every op it holds, forward and
    backward, down to attention)."""
    with jax.named_scope("attn"):
        h = L.rms_norm(lp["norm1"], x, cfg.norm_eps)
        if cfg.mla:
            return L.mla_block(lp["attn"], h, n_heads=cfg.n_heads,
                               qk_nope=cfg.qk_nope, qk_rope=cfg.qk_rope,
                               v_dim=cfg.v_head_dim,
                               rope_theta=cfg.rope_theta,
                               chunk=cfg.attn_chunk)
        return L.attention_block(lp["attn"], h, n_heads=cfg.n_heads,
                                 n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
                                 rope_theta=cfg.rope_theta, window=window,
                                 chunk=cfg.attn_chunk,
                                 pat=_attn_pat(cfg, pat), layer=layer_idx)


def _ffn(cfg, lp, x, pat, layer_idx):
    """The FFN sub-layer and its pre-norm, under the ``ffn`` scope."""
    with jax.named_scope("ffn"):
        h = L.rms_norm(lp["norm2"], x, cfg.norm_eps)
        return L.ffn_block(lp["ffn"], h, _ffn_pat(cfg, pat), layer=layer_idx)


def _run_dense(cfg, lp, x, pat, layer_idx, window):
    x = x + _attn(cfg, lp, x, pat, layer_idx, window)
    return x + _ffn(cfg, lp, x, pat, layer_idx), jnp.zeros((), jnp.float32)


def _run_moe(cfg, lp, x, pat, layer_idx, window):
    x = x + _attn(cfg, lp, x, pat, layer_idx, window)
    h = L.rms_norm(lp["norm2"], x, cfg.norm_eps)
    if cfg.moe_impl == "ep_shardmap":
        f, aux = L.moe_block_ep(lp["moe"], h, top_k=cfg.top_k,
                                n_experts=cfg.n_experts,
                                capacity_factor=cfg.capacity_factor,
                                pat=_moe_pat(cfg, pat), layer=layer_idx)
    else:
        f, aux = L.moe_block(lp["moe"], h, top_k=cfg.top_k,
                             capacity_factor=cfg.capacity_factor,
                             pat=_moe_pat(cfg, pat), layer=layer_idx)
    return x + f, aux


def _run_ssm(cfg, lp, x, pat, layer_idx):
    h = L.rms_norm(lp["norm1"], x, cfg.norm_eps)
    m = L.mamba2_block(lp["ssm"], h, d_state=cfg.ssm_state,
                       headdim=cfg.ssm_headdim, expand=cfg.ssm_expand,
                       d_conv=cfg.d_conv, chunk=cfg.ssd_chunk,
                       pat=_ssm_pat(cfg, pat), layer=layer_idx)
    return x + m, jnp.zeros((), jnp.float32)


def _run_shared_attn(cfg, sp, x, x0, pat, layer_idx):
    with jax.named_scope("attn"):
        h2 = jnp.concatenate([x, x0], -1)
        h2 = L.rms_norm(sp["norm1"], h2, cfg.norm_eps)
        a = L.attention_block(sp["attn"], h2, n_heads=cfg.n_heads,
                              n_kv=cfg.n_kv_heads,
                              head_dim=2 * cfg.d_model // cfg.n_heads,
                              rope_theta=cfg.rope_theta, window=None,
                              chunk=cfg.attn_chunk,
                              pat=_attn_pat(cfg, pat), layer=layer_idx)
    x = x + a
    return x + _ffn(cfg, sp, x, pat, layer_idx)


def _ffn_pat(cfg, pat) -> plan_mod.BoundPlan:
    bp = plan_mod.as_bound(pat)
    return dataclasses.replace(bp, nb=cfg.pattern_nb) if bp.active else bp


def _moe_pat(cfg, pat) -> plan_mod.BoundPlan:
    bp = plan_mod.as_bound(pat)
    if not bp.active:
        return bp
    fam = plan_mod.get_family(bp.family)
    if fam.expert_granular:
        # expert-granular: nb = the expert count; need dp | E and enough
        # kept experts to satisfy top-k, else the layer runs dense
        if (cfg.n_experts % bp.dp == 0
                and cfg.top_k <= cfg.n_experts // bp.dp):
            return dataclasses.replace(bp, nb=cfg.n_experts)
        return plan_mod.IDENTITY
    # hidden-granular: experts have their own (smaller) hidden dim; reuse
    # nb if it divides
    nb = cfg.pattern_nb
    while cfg.moe_d_ff % nb != 0:
        nb //= 2
    return dataclasses.replace(bp, nb=nb)


def _ssm_pat(cfg, pat) -> plan_mod.BoundPlan:
    # head-granular for SSD (nb = n_heads, dp must divide the head count);
    # state-row-granular for ssm_row (nb = d_state, dp must divide it);
    # families with neither adaptation run the SSM dense
    bp = plan_mod.as_bound(pat)
    if not bp.active:
        return bp
    fam = plan_mod.get_family(bp.family)
    if fam.head_granular and cfg.ssm_heads % bp.dp == 0:
        return dataclasses.replace(bp, nb=cfg.ssm_heads)
    if fam.ssm_state_granular and cfg.ssm_state % bp.dp == 0:
        return dataclasses.replace(bp, nb=cfg.ssm_state)
    return plan_mod.IDENTITY


def _attn_pat(cfg, pat) -> plan_mod.BoundPlan:
    # KV-group-granular attention dropout: nb = n_kv_heads so one dropped
    # unit is one KV head plus its GQA query-head group (contiguous in the
    # group-major head layout); families without attn_head_granular — and
    # MLA blocks, which have no per-head KV projections to slice — run the
    # attention dense
    bp = plan_mod.as_bound(pat)
    if (bp.active and plan_mod.get_family(bp.family).attn_head_granular
            and cfg.n_kv_heads % bp.dp == 0):
        return dataclasses.replace(bp, nb=cfg.n_kv_heads)
    return plan_mod.IDENTITY


def _window_for(cfg, i_arr, S):
    """Per-layer window scalar: sliding for local layers, 'infinite' for
    global ones (traced through the scan)."""
    if cfg.sliding_window is None:
        return None
    if cfg.global_every <= 0:
        return jnp.full_like(i_arr, cfg.sliding_window)
    is_global = (i_arr + 1) % cfg.global_every == 0
    return jnp.where(is_global, jnp.int32(1 << 30),
                     jnp.int32(cfg.sliding_window))


def forward(cfg: ModelConfig, params, tokens, pat=NO_PATTERN,
            vision_embeds=None):
    """Train-path forward.  tokens: [B, S] (or [B, K, S] for codebooks).
    ``pat``: a core.plan.BoundPlan (or the legacy PatternArgs shim).
    Returns (logits[f32], aux_loss)."""
    pat = plan_mod.as_bound(pat)
    with jax.named_scope("embed"):
        if cfg.n_codebooks:
            B, K, S = tokens.shape
            x = jnp.zeros((B, S, cfg.d_model), cfg.jdtype)
            for c in range(K):
                x = x + jnp.take(params["embed"]["tok"][c], tokens[:, c],
                                 axis=0)
        else:
            B, S = tokens.shape
            x = L.embed_tokens(params["embed"], tokens)
    if cfg.vision_tokens and vision_embeds is not None:
        vp = params["vision_proj"]
        v = L.rms_norm(vp["norm"], vision_embeds, cfg.norm_eps)
        v = jax.nn.gelu(v @ vp["w1"]) @ vp["w2"]
        x = jnp.concatenate([v.astype(x.dtype), x], 1)
        S = x.shape[1]
    x = constrain(x, ("batch", "res_seq", "embed"))

    # NOTE: the paper applies ONE pattern to the whole network per iteration
    # (§III-D), so a single static (dp, bias) for every layer is faithful —
    # and is what makes scan-over-layers work with static compact shapes.
    x0 = x if cfg.family == "hybrid" else None
    aux_total = jnp.zeros((), jnp.float32)
    layer_idx = 0
    stack_i = 0
    for kind, count in layer_groups(cfg):
        if kind == "attn_shared":
            x = _run_shared_attn(cfg, params["shared_attn"], x, x0, pat, 0)
            layer_idx += count
            continue
        stack = params["stacks"][stack_i]
        stack_i += 1
        window = _window_for(cfg, layer_idx + jnp.arange(count), S)

        def body(carry, xs, _kind=kind, _windowed=window is not None):
            x, aux = carry
            lp, win = xs if _windowed else (xs, None)
            if _kind == "dense":
                x, a = _run_dense(cfg, lp, x, pat, 0, win)
            elif _kind == "moe":
                x, a = _run_moe(cfg, lp, x, pat, 0, win)
            else:
                x, a = _run_ssm(cfg, lp, x, pat, 0)
            return (x, aux + a), None

        if cfg.remat and cfg.remat_policy == "dots":
            body_fn = jax.checkpoint(
                body, policy=jax.checkpoint_policies.dots_saveable)
        elif cfg.remat:
            body_fn = jax.checkpoint(body)
        else:
            body_fn = body
        xs = stack if window is None else (stack, window)
        (x, aux_total), _ = jax.lax.scan(body_fn, (x, aux_total), xs)
        layer_idx += count

    with jax.named_scope("head"):
        x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
        if cfg.n_codebooks:
            logits = jnp.einsum("bsd,kdv->bksv", x,
                                params["heads"]).astype(jnp.float32)
        else:
            logits = L.unembed(params["embed"], x)
        if cfg.logit_softcap > 0:
            logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return logits, aux_total


# --------------------------------------------------------------------------
# loss
# --------------------------------------------------------------------------

def lm_loss(cfg: ModelConfig, params, batch, pat=NO_PATTERN):
    """batch: {tokens, labels, [vision_embeds]}.  ``pat``: a BoundPlan or
    legacy PatternArgs.  Returns (loss, metrics)."""
    logits, aux = forward(cfg, params, batch["tokens"], pat,
                          batch.get("vision_embeds"))
    labels = batch["labels"]
    if cfg.vision_tokens and "vision_embeds" in batch:
        pad = jnp.full(labels.shape[:-1] + (cfg.vision_tokens,), -1,
                       labels.dtype)
        labels = jnp.concatenate([pad, labels], -1)
    with jax.named_scope("head"):
        mask = (labels >= 0).astype(jnp.float32)
        safe = jnp.maximum(labels, 0)
        logp = jax.nn.log_softmax(logits, -1)
        nll = -jnp.take_along_axis(logp, safe[..., None], -1)[..., 0]
        loss = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    total = loss + 0.01 * aux
    if cfg.mtp:
        total = total + 0.3 * _mtp_loss(cfg, params, batch, pat)
    return total, {"ce": loss, "aux": aux}


def _mtp_loss(cfg, params, batch, pat):
    """DeepSeek-style depth-1 multi-token prediction: predict t+2 from the
    embedding of t combined with the embedding of t+1, through one extra
    transformer block (DeepSeek-V3 feeds the trunk hidden instead of the
    t-embedding; we use the embedding to avoid a second trunk pass — the
    MTP block's params/FLOPs are identical, noted in DESIGN.md)."""
    tokens, labels = batch["tokens"], batch["labels"]
    x_prev = L.embed_tokens(params["embed"], tokens[:, :-1])
    x_next = L.embed_tokens(params["embed"], tokens[:, 1:])
    h = jnp.concatenate([x_prev, x_next], -1) @ params["mtp"]["proj"]
    h, _ = _run_dense(cfg, params["mtp"]["layer"], h, pat, 0, None)
    h = L.rms_norm(params["final_norm"], h, cfg.norm_eps)
    logits2 = L.unembed(params["embed"], h)
    lbl = labels[:, 1:]
    mask = (lbl >= 0).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits2, -1)
    nll = -jnp.take_along_axis(logp, jnp.maximum(lbl, 0)[..., None], -1)[..., 0]
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
