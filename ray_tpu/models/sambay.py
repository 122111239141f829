"""SambaY decoder-hybrid-decoder (Phi-4-mini-flash-reasoning style), TPU-first.

A decoder whose second half reads what its first half made (arXiv:
2507.06607; microsoft/Phi-4-mini-flash-reasoning, model_type phi4flash).
For N layers (N % 4 == 0), h = N // 2, layer i is

    even i <= h      Mamba-1 (models.decoder.mamba1 over ops.selective_scan);
                     layer h's scan output m is handed to the layers after it
    odd  i <  h      differential attention over a window of `sliding_window`
    i == h + 1       differential attention over the whole sequence; its
                     keys and values are handed to the layers after it
    even i >  h      a gated memory unit over layer h's m
    odd  i >  h + 1  differential attention of its own queries over layer
                     h + 1's keys and values (cross attention)

with no positional encoding anywhere, LayerNorm with bias, a SwiGLU MLP from
one fused input matrix in every layer, and the embedding as the head. The
kinds follow from N and `mb_per_layer` by that rule (`layer_kinds`), not
from a list, and are models.decoder's own (MAMBA1, DIFF_WINDOWED, DIFF_FULL,
GMU, DIFF_CROSS): `decoder()` hands them on as they are.
`SambaYConfig.phi4_mini_flash()` is the published config.json with the
sizes it does not give (Mamba-1's state 16, 4 taps, expansion 2, dt_rank
d / 16) at the family's convention.

Same conventions as models.gpt: dict pytrees, logical axis tables, bf16
matmuls; float32 norms, softplus, decays, state and lambdas.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops.loss import cross_entropy
from .decoder import (DIFF_CROSS, DIFF_FULL, DIFF_WINDOWED, GMU, MAMBA1,
                      Decoder, decoder_hidden, decoder_logits,
                      fused_swiglu_mlp, keep_kernel_outputs)

# The five kinds are models.decoder's own, under the model's names for them.
MAMBA, WINDOWED, FULL, CROSS = MAMBA1, DIFF_WINDOWED, DIFF_FULL, DIFF_CROSS


@dataclasses.dataclass(frozen=True)
class SambaYConfig:
    """Fields carry config.json's names where this repo has none of its
    own (d_model = hidden_size, d_ff = intermediate_size, n_heads /
    n_kv_heads = num_attention_heads / num_key_value_heads)."""
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_kv_heads: int = 4
    n_layers: int = 8
    d_ff: int = 2048
    mb_per_layer: int = 2
    sliding_window: int = 512
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0              # 0: ceil(d_model / 16)
    norm_eps: float = 1e-5
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    remat: bool = True

    def __post_init__(self):
        assert self.n_layers % 4 == 0, self.n_layers
        assert self.mb_per_layer == 2, "the rule below is the model's at 2"
        assert self.n_heads % self.n_kv_heads == 0
        assert self.n_heads % 2 == 0 and self.n_kv_heads % 2 == 0

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def mamba_inner(self) -> int:
        return self.mamba_expand * self.d_model

    @property
    def dt_rank(self) -> int:
        return self.mamba_dt_rank or math.ceil(self.d_model / 16)

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        h = self.n_layers // 2

        def kind(i: int) -> str:
            if i % self.mb_per_layer == 0:
                return MAMBA if i <= h else GMU
            if i < h:
                return WINDOWED
            return FULL if i == h + 1 else CROSS
        return tuple(kind(i) for i in range(self.n_layers))

    def decoder(self) -> Decoder:
        """Differential attention with no rotary, scores scaled by
        1 / sqrt(head_dim), WINDOWED layers over `sliding_window`; the
        fused SwiGLU MLP; under `remat` a block keeps what its kernels
        made and makes the rest again."""
        return Decoder(
            n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
            head_dim=self.head_dim, mlp=(fused_swiglu_mlp,) * self.n_layers,
            remat=keep_kernel_outputs if self.remat else None,
            kinds=self.layer_kinds,
            rope_base=None, norm_eps=self.norm_eps,
            window=self.sliding_window)

    def init(self, key) -> Dict:
        return sambay_init(key, self)

    @classmethod
    def tiny(cls, n_layers: int = 8) -> "SambaYConfig":
        """The CPU tests' size: 128 Mamba-1 channels (one row of lanes), 4
        query heads over 2 kv heads x 16 (two pairs over one), a window
        of 8."""
        return cls(vocab_size=256, d_model=64, n_heads=4, n_kv_heads=2,
                   n_layers=n_layers, d_ff=96, sliding_window=8,
                   mamba_d_state=4, max_seq_len=64)

    @classmethod
    def phi4_mini_flash(cls) -> "SambaYConfig":
        """microsoft/Phi-4-mini-flash-reasoning: 32 layers (9 Mamba-1, 8
        windowed, 1 full, 7 GMU, 7 cross), 3.85 B parameters."""
        return cls(vocab_size=200064, d_model=2560, n_heads=40,
                   n_kv_heads=20, n_layers=32, d_ff=10240, mb_per_layer=2,
                   sliding_window=512, norm_eps=1e-5, max_seq_len=262144)


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def _mamba_init(key, cfg: SambaYConfig, out_scale: float) -> Dict:
    """Mamba-1's own initialisation where config.json gives none: A =
    -(1 .. N) for every channel, the step's bias the inverse softplus of
    a log-uniform [1e-3, 1e-1], dt_proj uniform in +-1/sqrt(rank), D = 1,
    the convolution as torch's Conv1d (uniform in +-1/sqrt(taps))."""
    k_in, k_out, k_cw, k_cb, k_x, k_dtw, k_dt = jax.random.split(key, 7)
    d, inner, N = cfg.d_model, cfg.mamba_inner, cfg.mamba_d_state
    taps, rank = cfg.mamba_d_conv, cfg.dt_rank
    dt = jnp.exp(jax.random.uniform(
        k_dt, (inner,), minval=math.log(1e-3), maxval=math.log(1e-1)))
    bound = taps ** -0.5
    return {
        "in_proj": _normal(k_in, (d, 2 * inner), d ** -0.5, cfg.dtype),
        "conv_w": jax.random.uniform(
            k_cw, (inner, taps), minval=-bound, maxval=bound
        ).astype(cfg.dtype),
        "conv_b": jax.random.uniform(
            k_cb, (inner,), minval=-bound, maxval=bound).astype(cfg.dtype),
        "x_proj": _normal(k_x, (inner, rank + 2 * N), inner ** -0.5,
                          cfg.dtype),
        "dt_proj": jax.random.uniform(
            k_dtw, (rank, inner), minval=-rank ** -0.5,
            maxval=rank ** -0.5).astype(cfg.dtype),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jnp.broadcast_to(
            jnp.arange(1, N + 1, dtype=jnp.float32), (inner, N))),
        "D": jnp.ones((inner,), jnp.float32),
        "out_proj": _normal(k_out, (inner, d), inner ** -0.5 * out_scale,
                            cfg.dtype),
    }


def _gmu_init(key, cfg: SambaYConfig, out_scale: float) -> Dict:
    k_in, k_out = jax.random.split(key)
    d, inner = cfg.d_model, cfg.mamba_inner
    return {"gmu_in": _normal(k_in, (d, inner), d ** -0.5, cfg.dtype),
            "gmu_out": _normal(k_out, (inner, d), inner ** -0.5 * out_scale,
                               cfg.dtype)}


def _attention_init(key, cfg: SambaYConfig, out_scale: float,
                    cross: bool) -> Dict:
    """Differential attention: the projections with bias, four lambda
    vectors (normal, 0.1) and the sub-norm's weight over a pair's
    2 x head_dim columns. A cross layer holds `wq` alone."""
    kq, ko, *kl = jax.random.split(key, 6)
    d, hd = cfg.d_model, cfg.head_dim
    kv_d = cfg.n_kv_heads * hd
    if cross:
        mine = {"wq": _normal(kq, (d, d), d ** -0.5, cfg.dtype),
                "bq": jnp.zeros((d,), cfg.dtype)}
    else:
        mine = {"wqkv": _normal(kq, (d, d + 2 * kv_d), d ** -0.5, cfg.dtype),
                "bqkv": jnp.zeros((d + 2 * kv_d,), cfg.dtype)}
    lambdas = {name: 0.1 * jax.random.normal(k, (hd,)) for name, k in zip(
        ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"), kl)}
    return {**mine, **lambdas,
            "sub_norm": jnp.ones((2 * hd,), jnp.float32),
            "wo": _normal(ko, (d, d), d ** -0.5 * out_scale, cfg.dtype),
            "bo": jnp.zeros((d,), cfg.dtype)}


def _layer_init(key, kind: str, cfg: SambaYConfig) -> Dict:
    k_mix, k1, k2 = jax.random.split(key, 3)
    d, f = cfg.d_model, cfg.d_ff
    out_scale = (2 * cfg.n_layers) ** -0.5
    if kind == MAMBA:
        mixer = _mamba_init(k_mix, cfg, out_scale)
    elif kind == GMU:
        mixer = _gmu_init(k_mix, cfg, out_scale)
    else:
        mixer = _attention_init(k_mix, cfg, out_scale, kind == CROSS)
    return {
        "ln1": jnp.ones((d,), jnp.float32),
        "ln1_b": jnp.zeros((d,), jnp.float32),
        **mixer,
        "ln2": jnp.ones((d,), jnp.float32),
        "ln2_b": jnp.zeros((d,), jnp.float32),
        "fc1": _normal(k1, (d, 2 * f), d ** -0.5, cfg.dtype),
        "fc2": _normal(k2, (f, d), f ** -0.5 * out_scale, cfg.dtype),
    }


def sambay_init(key, cfg: SambaYConfig) -> Dict:
    keys = jax.random.split(key, cfg.n_layers + 1)
    return {
        "embed": _normal(keys[0], (cfg.vocab_size, cfg.d_model),
                         cfg.d_model ** -0.5, cfg.dtype),
        "lnf": jnp.ones((cfg.d_model,), jnp.float32),
        "lnf_b": jnp.zeros((cfg.d_model,), jnp.float32),
        "layers": [_layer_init(keys[i + 1], kind, cfg)
                   for i, kind in enumerate(cfg.layer_kinds)],
    }


def sambay_param_axes(cfg: SambaYConfig) -> Dict:
    every = {"ln1": ("embed",), "ln1_b": ("embed",), "ln2": ("embed",),
             "ln2_b": ("embed",), "fc1": ("embed", "mlp"),
             "fc2": ("mlp", "embed")}
    attention = {"lambda_q1": (None,), "lambda_k1": (None,),
                 "lambda_q2": (None,), "lambda_k2": (None,),
                 "sub_norm": (None,), "wo": ("mlp", "embed"),
                 "bo": ("embed",)}
    own = {**attention, "wqkv": ("embed", "mlp"), "bqkv": ("mlp",)}
    mixers = {
        MAMBA: {"in_proj": ("embed", None), "conv_w": (None, None),
                "conv_b": (None,), "x_proj": (None, None),
                "dt_proj": (None, None), "dt_bias": (None,),
                "A_log": (None, None), "D": (None,),
                "out_proj": (None, "embed")},
        GMU: {"gmu_in": ("embed", None), "gmu_out": (None, "embed")},
        WINDOWED: own, FULL: own,
        CROSS: {**attention, "wq": ("embed", "mlp"), "bq": ("mlp",)},
    }
    return {
        "embed": ("vocab", "embed"),
        "lnf": ("embed",), "lnf_b": ("embed",),
        "layers": [{**every, **mixers[kind]} for kind in cfg.layer_kinds],
    }


def sambay_forward(params: Dict, tokens, cfg: SambaYConfig):
    """tokens [batch, seq] int32 -> logits [batch, seq, vocab] fp32."""
    x, head, _, _ = decoder_hidden(params, tokens, cfg.decoder())
    return decoder_logits(x, head)


def sambay_loss(params: Dict, batch: Tuple, cfg: SambaYConfig):
    """Next-token cross entropy over the tied head."""
    tokens, targets = batch
    x, head, _, _ = decoder_hidden(params, tokens, cfg.decoder())
    return cross_entropy(x, head, targets)


def make_sambay_train_step(cfg: SambaYConfig, optimizer=None,
                           donate: bool = True, mesh=None, rules=None):
    """(init_state, jitted train_step), as models.gpt.make_train_step."""
    from ._training import make_train_step_for

    return make_train_step_for(
        lambda key: sambay_init(key, cfg),
        lambda params, batch: sambay_loss(params, batch, cfg),
        axes=sambay_param_axes(cfg), optimizer=optimizer, donate=donate,
        mesh=mesh, rules=rules)
