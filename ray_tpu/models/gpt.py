"""Flagship model: decoder-only transformer (GPT family), TPU-first.

Role in the framework: the model the reference's ML baselines fine-tune
with external torch code (BASELINE.md GPT-2 fine-tune config) exists here
natively — bf16 matmuls for the MXU, fp32 norms/softmax, rotary attention
via the Pallas flash kernel, logical-axis annotations so
parallel.partition rule tables shard it for TP/FSDP/SP without touching
model code, and `jax.checkpoint` rematerialization on each block to trade
FLOPs for HBM.

Params are a plain dict pytree; `gpt_param_axes` returns the matching
pytree of logical axis tuples.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops.loss import cross_entropy
from .decoder import (ATTENTION, Decoder, decoder_hidden, decoder_logits,
                      gelu_mlp)


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 6
    d_ff: int = 2048
    max_seq_len: int = 1024
    dtype: Any = jnp.bfloat16
    remat: bool = True
    tie_embeddings: bool = True

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def decoder(self) -> Decoder:
        """MHA from a fused `wqkv`, rotary positions and RMSNorm at the
        defaults of ops.layers, a GELU MLP."""
        # dots-saveable: keep matmul outputs, recompute elementwise (full
        # recompute only pays off when memory is the binding constraint;
        # callers can still pass remat=False to skip remat). It reads no
        # names, so the attention kernel's forward runs again under it.
        # The policy measured on this chip is decoder.keep_kernel_outputs
        # (OLMoE, PR 28); this literal waits for a cell that runs gpt
        # with remat on (ROADMAP C1(e)).
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return Decoder(
            n_heads=self.n_heads, n_kv_heads=self.n_heads,
            head_dim=self.head_dim, mlp=(gelu_mlp,) * self.n_layers,
            remat=policy if self.remat else None,
            kinds=(ATTENTION,) * self.n_layers)

    def init(self, key) -> Dict:
        return gpt_init(key, self)

    @classmethod
    def gpt2_small(cls) -> "GPTConfig":
        """GPT-2 124M-equivalent (the reference's fine-tune baseline)."""
        return cls(vocab_size=50304, d_model=768, n_heads=12, n_layers=12,
                   d_ff=3072, max_seq_len=1024)

    @classmethod
    def tiny(cls) -> "GPTConfig":
        return cls(vocab_size=512, d_model=64, n_heads=4, n_layers=2,
                   d_ff=128, max_seq_len=128)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _layer_init(key, cfg: GPTConfig) -> Dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d, f = cfg.d_model, cfg.d_ff
    scale = d ** -0.5
    out_scale = scale / (2 * cfg.n_layers) ** 0.5
    return {
        "ln1": jnp.ones((d,), dtype=jnp.float32),
        "wqkv": (jax.random.normal(k1, (d, 3 * d)) * scale
                 ).astype(cfg.dtype),
        "wo": (jax.random.normal(k2, (d, d)) * out_scale
               ).astype(cfg.dtype),
        "ln2": jnp.ones((d,), dtype=jnp.float32),
        "w1": (jax.random.normal(k3, (d, f)) * scale).astype(cfg.dtype),
        "w2": (jax.random.normal(k4, (f, d)) * out_scale
               ).astype(cfg.dtype),
    }


def gpt_init(key, cfg: GPTConfig) -> Dict:
    keys = jax.random.split(key, cfg.n_layers + 2)
    params = {
        "embed": (jax.random.normal(keys[0],
                                    (cfg.vocab_size, cfg.d_model))
                  * cfg.d_model ** -0.5).astype(cfg.dtype),
        "lnf": jnp.ones((cfg.d_model,), dtype=jnp.float32),
        "layers": [_layer_init(keys[i + 1], cfg)
                   for i in range(cfg.n_layers)],
    }
    if not cfg.tie_embeddings:
        params["head"] = (jax.random.normal(
            keys[-1], (cfg.d_model, cfg.vocab_size))
            * cfg.d_model ** -0.5).astype(cfg.dtype)
    return params


def gpt_param_axes(cfg: GPTConfig) -> Dict:
    """Logical axis names per parameter (parallel.partition rule input)."""
    layer = {
        "ln1": ("embed",),
        "wqkv": ("embed", "mlp"),   # heads concat: shard like mlp over tp
        "wo": ("mlp", "embed"),
        "ln2": ("embed",),
        "w1": ("embed", "mlp"),
        "w2": ("mlp", "embed"),
    }
    axes = {
        "embed": ("vocab", "embed"),
        "lnf": ("embed",),
        "layers": [dict(layer) for _ in range(cfg.n_layers)],
    }
    if not cfg.tie_embeddings:
        axes["head"] = ("embed", "vocab")
    return axes


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def gpt_forward(params: Dict, tokens, cfg: GPTConfig):
    """tokens [batch, seq] int32 -> logits [batch, seq, vocab] (fp32)."""
    x, head, _, _ = decoder_hidden(params, tokens, cfg.decoder())
    return decoder_logits(x, head)


def gpt_loss(params: Dict, batch: Tuple, cfg: GPTConfig):
    """Next-token cross entropy; batch = (tokens, targets) [b, s].

    ops.loss.cross_entropy: chunked over rows with the gradient taken in
    the same pass, so the f32 [b, s, vocab] logits of the naive
    formulation (12.3 GB at B=64/S=1024/V=50k — it OOMs a v5e chip) are
    held neither in the forward nor for the backward pass."""
    tokens, targets = batch
    x, head, _, _ = decoder_hidden(params, tokens, cfg.decoder())
    return cross_entropy(x, head, targets)


# ---------------------------------------------------------------------------
# training step
# ---------------------------------------------------------------------------
def make_train_step(cfg: GPTConfig, optimizer=None,
                    donate: bool = True,
                    mesh=None, rules=None):
    """Build (init_state, train_step). train_step is jit-compiled; with a
    mesh + partition rules, params/opt-state carry NamedShardings and XLA
    inserts the dp gradient psum / tp collectives from the shardings
    (scaling-book recipe — no explicit pmap/DDP wrapper)."""
    from ._training import make_train_step_for

    return make_train_step_for(
        lambda key: gpt_init(key, cfg),
        lambda params, batch: gpt_loss(params, batch, cfg),
        axes=gpt_param_axes(cfg), optimizer=optimizer, donate=donate,
        mesh=mesh, rules=rules)


def shard_params(params: Dict, cfg: GPTConfig, mesh, rules):
    """Place a param pytree onto a mesh per the logical-axis rule table."""
    from ._training import place_params

    return place_params(params, gpt_param_axes(cfg), mesh, rules)


def shard_batch(batch, mesh, axis: str = "dp"):
    from jax.sharding import NamedSharding, PartitionSpec
    sharding = NamedSharding(mesh, PartitionSpec(axis))
    return jax.tree.map(lambda x: jax.device_put(x, sharding), batch)
