"""Model configurations: the Llama family (Llama 2/3, Mistral, Qwen2,
Gemma), Mixtral-style expert models, the DeepSeek-V2 family (latent
attention, softmax-routed experts beside shared ones, leading dense layers)
and the MiMo-V2 family (window layers with a learned sink beside
full-attention layers, keys wider than values, sigmoid-routed experts of
which a deployment's chip holds a share), the Granite 4.0-H family
(Mamba-2 layers beside attention) and the Xing4.0 family (`xing4_0`:
latent attention with LOW-RANK queries, sigmoid-routed experts beside a
shared one with a scaling factor, and a residual of `hc_mult` streams a
token mixed at every sublayer by manifold-constrained hyper-connections)
and the SDAR family (`sdar_moe`: grouped-query attention with a norm a
head on queries and keys, softmax-routed experts in every layer, generated
by diffusion over BLOCKS of positions under a block-causal mask).

One config dataclass covers the architectures the reference serves through
vLLM/sglang (reference: examples/llm/configs/*.yaml serve Llama/DeepSeek
distill models; lib/engines/* accept arbitrary HF models). The TPU build
owns the model natively, so the config is ours, not an engine passthrough.

Conventions:
- `head_dim` is explicit (Llama3 keeps hidden/heads, but e.g. Qwen2-0.5B
  differs), GQA via `num_kv_heads < num_heads`.
- `rope_scaling` carries the Llama-3.1 long-context NTK scaling dict, or
  the `deepseek_v2` YaRN dict (ops/rope.py reads both).
- latent attention (`kv_lora_rank > 0`): queries are `num_heads x
  (qk_nope_head_dim + qk_rope_head_dim)`, the cache keeps ONE row of
  `kv_lora_rank + qk_rope_head_dim` values a token a layer (`latent_width`)
  and `head_dim` is the query/key head size (nope + rope).
- expert layers: `num_experts` routed experts of `moe_intermediate_size`,
  `num_shared_experts` shared ones on every token, the first
  `first_dense_layers` layers dense at `intermediate_size`; the router's
  scoring and renormalisation are read here, never assumed (models/moe.py).
- a layer pattern (`layer_kinds`, one entry a layer: 0 full attention, 1
  window attention) makes a model HYBRID: each kind has its own KV heads,
  key width, value width and rope base (`attn_kind`), its own pools, page
  ids and block tables (docs/kv_cache.md "Window pools"). An empty
  pattern is every other model: one kind, `num_kv_heads` x `head_dim`.
- a pattern may name MAMBA layers (granitemoehybrid): a Mamba-2 mixer in
  place of attention, which keeps no pages but a fixed-size state a
  sequence (`recurrent`; docs/kv_cache.md "State pools"). The attention
  layers beside them are ONE kind with one block table unless the pattern
  also names WINDOW layers.
- an expert layer may hold a SHARE of the experts: the router scores all
  `num_experts`, the layer holds `experts_held` of them from
  `expert_offset` (0 held = all of them, every other preset).
- low-rank queries (`q_lora_rank > 0`, latent attention only): `q =
  RMSNorm(x W_qa) W_qb` in place of one matrix `W_q` (0 = one matrix,
  every other preset).
- the residual convention: every preset but one carries ONE stream a
  token, `x [B, T, D]`, and a sublayer adds to it (`hc_mult` 1). With
  `hc_mult` n > 1 a token carries n streams, `X [B, T, n, D]`: each
  sublayer reads a learned, input-dependent mix of them and writes back
  through a doubly-stochastic n x n matrix made by `hc_sinkhorn_iters`
  Sinkhorn iterations on `exp(clamp(., -hc_res_clamp, hc_res_clamp))`
  (models/mhc.py). `forward` copies the embedding into the n streams and
  sums them before the final norm, so nothing outside `models/` sees
  them; such a model runs on ONE device (the stage executors and every
  mesh axis refuse it).
- dtypes: weights/activations bfloat16 on TPU (MXU-native), float32 for
  norms/softmax accumulation inside the ops.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any, NamedTuple, Optional

# the kinds a layer pattern names: full attention, window attention, and
# a Mamba-2 mixer, which keeps no pages (a fixed-size state a sequence)
FULL, WINDOW, MAMBA = 0, 1, 2

# how a block-diffusion model chooses the masked positions a denoising
# pass fills: a fixed count a pass, the leftmost. The one transfer that is
# served: every other (the most confident `low_confidence_static`, the
# confidence thresholds) is refused by name until trained weights can say
# which a deployment runs
DLM_STRATEGY = "sequential"


class AttnKind(NamedTuple):
    """What one kind of attention layer keeps a token and how it reads:
    the cache specification the pools are sized from."""

    kv_heads: int
    k_dim: int        # key (and query) head width
    v_dim: int        # value head width
    rope_theta: float
    window: int       # tokens a query sees, itself included; 0 = all
    sink: bool        # a learned per-head logit in the softmax

    @property
    def k_width(self) -> int:
        return self.kv_heads * self.k_dim

    @property
    def v_width(self) -> int:
        return self.kv_heads * self.v_dim


@dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 500000.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    tie_word_embeddings: bool = False
    attn_bias: bool = False  # qwen2-style qkv bias
    rope_scaling: Optional[dict[str, Any]] = None
    dtype: str = "bfloat16"
    # gemma-family: GeGLU activation, sqrt(d)-scaled embeddings, and
    # (offset + w) norm-weight convention (gemma: 1.0)
    hidden_act: str = "silu"
    scale_embeddings: bool = False
    norm_weight_offset: float = 0.0
    # sparse MoE (mixtral-style): 0 experts = dense FFN
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # expert width (0 = intermediate_size, mixtral), shared experts on
    # every token, leading dense layers, and the router as published:
    # renormalise the top-k weights (mixtral: yes) and scale them
    moe_intermediate_size: int = 0
    num_shared_experts: int = 0
    first_dense_layers: int = 0
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # latent attention (deepseek_v2): 0 = plain / grouped-query heads
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # hybrid attention (mimo_v2_flash): the kind of each layer (empty =
    # one kind), the window kind's heads / widths / rope base, the share
    # of a head's dimensions that rotate, the window kind's learned sink
    # and the factor on every value row. `v_head_dim` above is then the
    # full kind's value width.
    layer_kinds: tuple = ()
    sliding_window: int = 0
    swa_num_kv_heads: int = 0
    swa_head_dim: int = 0
    swa_v_head_dim: int = 0
    swa_rope_theta: float = 10000.0
    rotary_dim: int = 0           # 0 = the whole head
    swa_sink: bool = False
    attn_value_scale: float = 1.0
    # the router as published: score function (softmax | sigmoid) and a
    # learned bias added to the scores for SELECTION only (noaux_tc)
    scoring_func: str = "softmax"
    router_bias: bool = False
    # the share of the experts this layer holds (0 = all `num_experts`)
    experts_held: int = 0
    expert_offset: int = 0
    # the Granite multipliers (granitemoehybrid): on the embedding, on each
    # block's output before the residual add, on the attention scores
    # (0 = head_dim ** -0.5) and the divisor of the logits; and whether
    # positions rotate queries and keys at all ("nope": they do not)
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attn_scale: float = 0.0
    logits_scaling: float = 1.0
    use_rope: bool = True
    # Mamba-2 mixer of the MAMBA layers: H heads of P, state N a head, G
    # groups of B / C, a causal depthwise convolution of `mamba_d_conv`
    # over [x | B | C], the chunk of the chunked (SSD) prefill form
    mamba_heads: int = 0
    mamba_head_dim: int = 0
    mamba_d_state: int = 0
    mamba_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk: int = 256
    mamba_conv_bias: bool = True
    # low-rank queries of latent attention (xing4_0; 0 = one matrix W_q)
    q_lora_rank: int = 0
    # the residual's streams a token (1 = one stream, a plain add) and,
    # for more, the maps of models/mhc.py: Sinkhorn iterations, the
    # epsilon in each of their divisions, the clamp on exp's argument
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: float = 30.0
    # of SEEDED weights only (a checkpoint's come from training), stated by
    # the preset so that no other preset's weights follow from them: the
    # routed experts' down-projection beside its fan-in scale and the
    # selection bias's deviation (moe.py: init_moe_params has the reasons)
    seed_expert_down_scale: float = 1.0
    seed_router_bias_std: float = 0.02
    # an RMSNorm over the head_dim values of every query and key head,
    # before the rotation (sdar_moe; False = none, every other preset)
    qk_norm: bool = False
    # generation by diffusion over blocks (sdar_moe; 0 = one token a row a
    # step, every other preset): a sequence is generated `block_length`
    # positions at a time from `mask_token_id`, `denoising_steps` passes
    # that each fill block_length / denoising_steps masked positions
    # chosen by `remasking_strategy` (`sequential`: the leftmost),
    # then one pass that writes the finished block's keys and values;
    # attention is causal by BLOCK (`k_pos // B <= q_pos // B`), the
    # logits at a position predict that position
    block_length: int = 0
    denoising_steps: int = 0
    remasking_strategy: str = ""
    mask_token_id: int = -1

    @property
    def dlm(self) -> bool:
        """Generation by diffusion over blocks: a step carries a whole
        block a sequence (engine/engine.py `_dlm_multi`)."""
        return self.block_length > 0

    @property
    def hybrid(self) -> bool:
        """More than one kind of ATTENTION layer: pools, page ids and
        block tables per kind."""
        return WINDOW in self.layer_kinds

    @property
    def recurrent(self) -> bool:
        """Some layers keep a fixed-size state a sequence and no pages."""
        return MAMBA in self.layer_kinds

    def layer_kind(self, layer: int) -> int:
        return self.layer_kinds[layer] if self.layer_kinds else FULL

    @property
    def paged_layers(self) -> tuple:
        """The layers that keep pages, in order: `KVCache.k[i]` is layer
        `paged_layers[i]`'s pool (every layer, but for a MAMBA layer)."""
        return tuple(l for l in range(self.num_layers)
                     if self.layer_kind(l) != MAMBA)

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def mamba_conv_width(self) -> int:
        """Channels the convolution runs over: [x | B | C]."""
        return self.mamba_inner + 2 * self.mamba_groups * self.mamba_d_state

    def attn_kind(self, kind: int) -> AttnKind:
        if kind == WINDOW:
            return AttnKind(
                self.swa_num_kv_heads, self.swa_head_dim,
                self.swa_v_head_dim, self.swa_rope_theta,
                self.sliding_window, self.swa_sink,
            )
        return AttnKind(
            self.num_kv_heads, self.head_dim,
            self.v_head_dim if self.hybrid else self.head_dim,
            self.rope_theta, 0, False,
        )

    def layers_of(self, kind: int) -> int:
        if not self.layer_kinds:
            return self.num_layers if kind == FULL else 0
        return sum(1 for k in self.layer_kinds if k == kind)

    @property
    def held_experts(self) -> int:
        return self.experts_held or self.num_experts

    @property
    def q_size(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_size(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def latent(self) -> bool:
        """Latent attention: one cached row a token, no K/V heads."""
        return self.kv_lora_rank > 0

    @property
    def latent_width(self) -> int:
        """Values a token keeps in a layer's latent pool: [c ; k_r]."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_pool_width(self) -> int:
        """Lanes a latent row OCCUPIES: `latent_width` rounded up to the
        128-lane tile (576 -> 640). HBM arrays are tiled by 128 lanes, so
        a [N, 576] pool takes 640 a row whatever its declared shape, and
        the page DMAs of a kernel must be whole tiles (Mosaic refuses a
        576-wide slice). The pool is declared at the width it occupies;
        the pad lanes are zero and sized into the pool's budget."""
        return -(-self.latent_width // 128) * 128

    @property
    def expert_width(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    def is_moe_layer(self, layer: int) -> bool:
        return bool(self.num_experts) and layer >= self.first_dense_layers

    def with_(self, **kw) -> "ModelConfig":
        return replace(self, **kw)

    @classmethod
    def from_hf_config(cls, hf: dict, name: str = "hf-model") -> "ModelConfig":
        """Build from a HuggingFace config.json dict (llama / mistral /
        qwen2 / gemma / mixtral / deepseek_v2 / mimo_v2_flash /
        granitemoehybrid / xing4_0 / sdar_moe)."""
        if hf.get("model_type") == "deepseek_v2":
            return cls._from_deepseek_v2(hf, name)
        if hf.get("model_type") == "mimo_v2_flash":
            return cls._from_mimo_v2_flash(hf, name)
        if hf.get("model_type") == "granitemoehybrid":
            return cls._from_granitemoehybrid(hf, name)
        if hf.get("model_type") == "xing4_0":
            return cls._from_xing4_0(hf, name)
        if hf.get("model_type") == "sdar_moe":
            return cls._from_sdar_moe(hf, name)
        num_heads = hf["num_attention_heads"]
        head_dim = hf.get("head_dim") or hf["hidden_size"] // num_heads
        return cls(
            name=name,
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=num_heads,
            num_kv_heads=hf.get("num_key_value_heads", num_heads),
            head_dim=head_dim,
            rope_theta=hf.get("rope_theta", 10000.0),
            rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
            max_position_embeddings=hf.get("max_position_embeddings", 8192),
            # GemmaConfig defaults tie_word_embeddings=True and
            # to_diff_dict drops default values from config.json
            tie_word_embeddings=hf.get(
                "tie_word_embeddings", hf.get("model_type") == "gemma"
            ),
            attn_bias=hf.get("model_type") == "qwen2",
            rope_scaling=hf.get("rope_scaling"),
            # published Gemma configs put "gelu" in hidden_act with the
            # real activation in hidden_activation; HF's GemmaMLP forces
            # gelu_pytorch_tanh when the latter is absent
            hidden_act=(
                hf.get("hidden_activation") or "gelu_pytorch_tanh"
            ) if hf.get("model_type") == "gemma" else "silu",
            scale_embeddings=hf.get("model_type") == "gemma",
            norm_weight_offset=1.0 if hf.get("model_type") == "gemma" else 0.0,
            num_experts=hf.get("num_local_experts", 0),
            num_experts_per_tok=hf.get("num_experts_per_tok", 2),
        )


    @classmethod
    def _from_deepseek_v2(cls, hf: dict, name: str) -> "ModelConfig":
        """The `deepseek_v2` keys. What this build cannot run is refused
        here, by name, rather than read as something else."""
        unsupported = {
            "q_lora_rank": hf.get("q_lora_rank") is not None,
            "scoring_func": hf.get("scoring_func", "softmax") != "softmax",
            "topk_method": hf.get("topk_method", "greedy") != "greedy",
            "n_group": hf.get("n_group", 1) not in (None, 1),
            "moe_layer_freq": hf.get("moe_layer_freq", 1) != 1,
            "attention_bias": bool(hf.get("attention_bias")),
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise ValueError(
                f"deepseek_v2 config: {bad[0]}={hf.get(bad[0])!r} is not "
                "served for this model_type (low-rank queries, a router "
                "other than greedy softmax, group-limited routing, expert "
                "layers at a period other than 1, attention bias)"
            )
        nope, rope = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
        return cls(
            name=name,
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf.get(
                "num_key_value_heads", hf["num_attention_heads"]
            ),
            head_dim=nope + rope,
            rope_theta=hf.get("rope_theta", 10000.0),
            rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
            max_position_embeddings=hf.get("max_position_embeddings", 8192),
            tie_word_embeddings=hf.get("tie_word_embeddings", False),
            rope_scaling=hf.get("rope_scaling"),
            num_experts=hf.get("n_routed_experts") or 0,
            num_experts_per_tok=hf.get("num_experts_per_tok", 2),
            moe_intermediate_size=hf.get("moe_intermediate_size", 0),
            num_shared_experts=hf.get("n_shared_experts") or 0,
            first_dense_layers=hf.get("first_k_dense_replace", 0),
            norm_topk_prob=bool(hf.get("norm_topk_prob", False)),
            routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
            kv_lora_rank=hf["kv_lora_rank"],
            qk_nope_head_dim=nope,
            qk_rope_head_dim=rope,
            v_head_dim=hf["v_head_dim"],
        )

    @classmethod
    def _from_mimo_v2_flash(cls, hf: dict, name: str) -> "ModelConfig":
        """The `mimo_v2_flash` keys: a layer pattern of window (1) and
        full (0) attention layers, each kind with its own KV heads and
        rope base, 192-wide keys over 128-wide values, sigmoid-routed
        experts chosen with a correction bias. What is not served is
        refused by name.

        A deployment's chip holds a SHARE of the experts: the file it
        runs states the experts it holds as `n_routed_experts` and the
        router's width as `router_width` (absent: the same), from
        `expert_offset` (absent: 0)."""
        freq = list(hf.get("moe_layer_freq") or [])
        n_layers = hf["num_hidden_layers"]
        first_dense = next((i for i, f in enumerate(freq) if f), len(freq))
        pattern = tuple(hf["hybrid_layer_pattern"])
        unsupported = {
            "n_group": hf.get("n_group", 1) not in (None, 1),
            "topk_group": hf.get("topk_group", 1) not in (None, 1),
            "attention_bias": bool(hf.get("attention_bias")),
            "add_full_attention_sink_bias":
                bool(hf.get("add_full_attention_sink_bias")),
            "routed_scaling_factor":
                hf.get("routed_scaling_factor") not in (None, 1, 1.0),
            "n_shared_experts": bool(hf.get("n_shared_experts")),
            "scoring_func": hf.get("scoring_func") != "sigmoid",
            "topk_method": hf.get("topk_method") != "noaux_tc",
            "moe_layer_freq":
                len(freq) != n_layers or not all(freq[first_dense:]),
            "hybrid_layer_pattern":
                len(pattern) != n_layers or set(pattern) - {FULL, WINDOW},
            "swa_num_attention_heads":
                hf.get("swa_num_attention_heads", hf["num_attention_heads"])
                != hf["num_attention_heads"],
            "sliding_window_size":
                hf.get("sliding_window_size", hf["sliding_window"])
                != hf["sliding_window"],
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise ValueError(
                f"mimo_v2_flash config: {bad[0]}={hf.get(bad[0])!r} is not "
                "served (group-limited routing, attention bias, a sink in "
                "full-attention layers, a routed scaling factor, shared "
                "experts beside a sigmoid router, a router other than "
                "sigmoid with a noaux_tc bias, dense layers after the first "
                "expert layer, a layer pattern that does not name every "
                "layer, window layers with another head count or window)"
            )
        held = hf.get("n_routed_experts") or 0
        return cls(
            name=name,
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=n_layers,
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_key_value_heads"],
            head_dim=hf["head_dim"],
            rope_theta=hf.get("rope_theta", 10000.0),
            rms_norm_eps=hf.get("layernorm_epsilon", 1e-5),
            max_position_embeddings=hf.get("max_position_embeddings", 8192),
            tie_word_embeddings=hf.get("tie_word_embeddings", False),
            num_experts=hf.get("router_width", held),
            num_experts_per_tok=hf.get("num_experts_per_tok", 2),
            moe_intermediate_size=hf.get("moe_intermediate_size", 0),
            first_dense_layers=first_dense,
            norm_topk_prob=bool(hf.get("norm_topk_prob", False)),
            v_head_dim=hf["v_head_dim"],
            layer_kinds=pattern,
            sliding_window=hf["sliding_window"],
            swa_num_kv_heads=hf["swa_num_key_value_heads"],
            swa_head_dim=hf["swa_head_dim"],
            swa_v_head_dim=hf["swa_v_head_dim"],
            swa_rope_theta=hf.get("swa_rope_theta", 10000.0),
            rotary_dim=int(
                hf["head_dim"] * hf.get("partial_rotary_factor", 1.0)
            ),
            swa_sink=bool(hf.get("add_swa_attention_sink_bias")),
            attn_value_scale=float(hf.get("attention_value_scale") or 1.0),
            scoring_func="sigmoid",
            router_bias=True,
            experts_held=held,
            expert_offset=hf.get("expert_offset", 0),
        )


    @classmethod
    def _from_granitemoehybrid(cls, hf: dict, name: str) -> "ModelConfig":
        """The `granitemoehybrid` keys: `layer_types` names each layer
        "mamba" (a Mamba-2 mixer) or "attention" (grouped-query heads, no
        position encoding), every layer followed by a SwiGLU of
        `shared_intermediate_size`; four multipliers. What is not served
        is refused by name."""
        types = list(hf.get("layer_types") or [])
        heads, hd = hf.get("mamba_n_heads", 0), hf.get("mamba_d_head", 0)
        groups = hf.get("mamba_n_groups", 1)
        unsupported = {
            "num_local_experts": bool(hf.get("num_local_experts")),
            "layer_types":
                len(types) != hf["num_hidden_layers"]
                or set(types) - {"mamba", "attention"},
            "position_embedding_type":
                hf.get("position_embedding_type") != "nope",
            "rope_scaling": hf.get("rope_scaling") is not None,
            # the decode step's kernel (ops/pallas_ssm.py): one B and one
            # C a row, whole heads side by side in the 128 lanes
            "mamba_n_groups": groups != 1,
            "mamba_d_head":
                hd < 1 or 128 % hd != 0 or heads % (128 // hd) != 0,
            "mamba_expand":
                hf.get("mamba_expand", 2) * hf["hidden_size"] != heads * hd,
            "mamba_proj_bias": bool(hf.get("mamba_proj_bias")),
            "attention_bias": bool(hf.get("attention_bias")),
            "hidden_act": hf.get("hidden_act", "silu") != "silu",
            "normalization_function":
                hf.get("normalization_function", "rmsnorm") != "rmsnorm",
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise ValueError(
                f"granitemoehybrid config: {bad[0]}={hf.get(bad[0])!r} is "
                "not served (routed experts, a layer type other than mamba "
                "or attention, a position encoding other than \"nope\", "
                "rope scaling, more than one group of B and C, a Mamba head "
                "size that does not pack whole heads into 128 lanes, "
                "an inner width other than heads x head size, a projection "
                "or attention bias, an activation other than silu, a norm "
                "other than rmsnorm)"
            )
        num_heads = hf["num_attention_heads"]
        return cls(
            name=name,
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["shared_intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=num_heads,
            num_kv_heads=hf.get("num_key_value_heads", num_heads),
            head_dim=hf.get("head_dim") or hf["hidden_size"] // num_heads,
            rope_theta=hf.get("rope_theta", 10000.0),
            rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
            max_position_embeddings=hf.get("max_position_embeddings", 8192),
            tie_word_embeddings=hf.get("tie_word_embeddings", True),
            layer_kinds=tuple(
                MAMBA if t == "mamba" else FULL for t in types),
            embedding_multiplier=float(hf.get("embedding_multiplier", 1.0)),
            residual_multiplier=float(hf.get("residual_multiplier", 1.0)),
            attn_scale=float(hf.get("attention_multiplier") or 0.0),
            logits_scaling=float(hf.get("logits_scaling", 1.0)),
            use_rope=False,
            mamba_heads=heads,
            mamba_head_dim=hd,
            mamba_d_state=hf["mamba_d_state"],
            mamba_groups=groups,
            mamba_d_conv=hf.get("mamba_d_conv", 4),
            mamba_chunk=hf.get("mamba_chunk_size", 256),
            mamba_conv_bias=bool(hf.get("mamba_conv_bias", True)),
        )

    @classmethod
    def _from_xing4_0(cls, hf: dict, name: str) -> "ModelConfig":
        """The `xing4_0` keys: the `deepseek_v3` layout (latent attention
        with low-rank queries, sigmoid-routed experts chosen with a
        correction bias, renormalised and scaled, beside shared ones,
        leading dense layers, YaRN) and a residual of `hc_mult` streams
        (`hc_*`, `mhc_h_res_clamp_*`). The multi-token-prediction layer
        (`num_nextn_predict_layers`) is not built: a server that does not
        draft from it drops it at load. What is not served is refused by
        name."""
        sc = hf.get("rope_scaling") or {}
        clamp = hf.get("mhc_h_res_clamp_max", 30)
        unsupported = {
            "n_group": hf.get("n_group", 1) not in (None, 1),
            "topk_group": hf.get("topk_group", 1) not in (None, 1),
            "ep_size": hf.get("ep_size", 1) not in (None, 1),
            "attention_bias": bool(hf.get("attention_bias")),
            "moe_layer_freq": hf.get("moe_layer_freq", 1) != 1,
            "scoring_func": hf.get("scoring_func") != "sigmoid",
            "topk_method": hf.get("topk_method") != "noaux_tc",
            "q_lora_rank": not hf.get("q_lora_rank"),
            "hidden_act": hf.get("hidden_act", "silu") != "silu",
            "rope_scaling":
                bool(sc) and (sc.get("type") != "yarn" or sc.get(
                    "mscale", 1.0) != sc.get("mscale_all_dim", 0.0)),
            "hc_mult": hf.get("hc_mult", 1) < 2,
            "mhc_h_res_clamp_min":
                hf.get("mhc_h_res_clamp_min", -clamp) != -clamp,
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise ValueError(
                f"xing4_0 config: {bad[0]}={hf.get(bad[0])!r} is not served "
                "(group-limited routing, experts over an ep group, "
                "attention bias, expert layers at a period other than 1, a "
                "router other than sigmoid with a noaux_tc bias, full-rank "
                "queries, an activation other than silu, rope scaling other "
                "than YaRN with mscale == mscale_all_dim, a residual of one "
                "stream, a clamp that is not symmetric)"
            )
        nope, rope = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
        return cls(
            name=name,
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf.get(
                "num_key_value_heads", hf["num_attention_heads"]
            ),
            head_dim=nope + rope,
            rope_theta=hf.get("rope_theta", 10000.0),
            rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
            max_position_embeddings=hf.get("max_position_embeddings", 8192),
            tie_word_embeddings=hf.get("tie_word_embeddings", False),
            rope_scaling=hf.get("rope_scaling"),
            num_experts=hf.get("n_routed_experts") or 0,
            num_experts_per_tok=hf.get("num_experts_per_tok", 2),
            moe_intermediate_size=hf.get("moe_intermediate_size", 0),
            num_shared_experts=hf.get("n_shared_experts") or 0,
            first_dense_layers=hf.get("first_k_dense_replace", 0),
            norm_topk_prob=bool(hf.get("norm_topk_prob", False)),
            routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
            kv_lora_rank=hf["kv_lora_rank"],
            qk_nope_head_dim=nope,
            qk_rope_head_dim=rope,
            v_head_dim=hf["v_head_dim"],
            scoring_func="sigmoid",
            router_bias=True,
            q_lora_rank=hf["q_lora_rank"],
            hc_mult=hf["hc_mult"],
            hc_sinkhorn_iters=hf.get("hc_sinkhorn_iters", 20),
            hc_eps=float(hf.get("hc_eps", 1e-6)),
            hc_res_clamp=float(clamp),
            **_XING_SEEDS,
        )

    @classmethod
    def _from_sdar_moe(cls, hf: dict, name: str) -> "ModelConfig":
        """The `sdar_moe` keys: grouped-query attention with an RMSNorm
        over every query and key head, softmax-routed experts in every
        layer (renormalised top-k, no shared expert), and the keys of the
        generation by diffusion over blocks that the served config.json
        states beside them (`block_length`, `denoising_steps`,
        `remasking_strategy`, `mask_token_id`). What is not served is
        refused by name."""
        block = hf.get("block_length", 0)
        steps = hf.get("denoising_steps", 0)
        unsupported = {
            "use_sliding_window": bool(hf.get("use_sliding_window")),
            "rope_scaling": hf.get("rope_scaling") is not None,
            "mlp_only_layers": bool(hf.get("mlp_only_layers")),
            "decoder_sparse_step": hf.get("decoder_sparse_step", 1) != 1,
            "attention_bias": bool(hf.get("attention_bias")),
            "hidden_act": hf.get("hidden_act", "silu") != "silu",
            "block_length":
                block < 2 or block & (block - 1) != 0,
            "denoising_steps": steps < 1 or block % max(steps, 1) != 0,
            "remasking_strategy":
                hf.get("remasking_strategy") != DLM_STRATEGY,
            "mask_token_id":
                not 0 <= hf.get("mask_token_id", -1) < hf["vocab_size"],
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise ValueError(
                f"sdar_moe config: {bad[0]}={hf.get(bad[0])!r} is not served "
                "(a sliding window, rope scaling, dense layers among the "
                "expert layers, expert layers at a period other than 1, "
                "attention bias, an activation other than silu, a block "
                "length that is not a power of two above 1, denoising steps "
                "that do not divide the block, a transfer strategy other "
                f"than {DLM_STRATEGY}: low_confidence_static is judged by "
                "no reference here and the confidence thresholds fill a "
                "data-dependent number of positions a pass, a mask token "
                "outside the vocabulary)"
            )
        return cls(
            name=name,
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_key_value_heads"],
            head_dim=hf["head_dim"],
            rope_theta=hf.get("rope_theta", 10000.0),
            rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
            max_position_embeddings=hf.get("max_position_embeddings", 8192),
            tie_word_embeddings=hf.get("tie_word_embeddings", False),
            num_experts=hf["num_experts"],
            num_experts_per_tok=hf["num_experts_per_tok"],
            moe_intermediate_size=hf["moe_intermediate_size"],
            norm_topk_prob=bool(hf.get("norm_topk_prob", False)),
            qk_norm=True,
            block_length=block,
            denoising_steps=steps,
            remasking_strategy=hf["remasking_strategy"],
            mask_token_id=hf["mask_token_id"],
            **_SDAR_SEEDS,
        )


_LLAMA31_SCALING = {
    "rope_type": "llama3",
    "factor": 8.0,
    "low_freq_factor": 1.0,
    "high_freq_factor": 4.0,
    "original_max_position_embeddings": 8192,
}

PRESETS: dict[str, ModelConfig] = {}


def _preset(cfg: ModelConfig) -> ModelConfig:
    PRESETS[cfg.name] = cfg
    return cfg

# Tiny config for CPU tests: dims respect TPU tiling multiples where cheap.
TINY = _preset(ModelConfig(
    name="tiny",
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    num_layers=2,
    num_heads=4,
    num_kv_heads=2,
    head_dim=16,
    rope_theta=10000.0,
    max_position_embeddings=2048,
    tie_word_embeddings=True,
))

# Llama-3.2 checkpoints were trained with rope factor 32 (not 3.1's 8).
_LLAMA32_SCALING = {**_LLAMA31_SCALING, "factor": 32.0}

# A ~1.2B debug/bench config (fits any single TPU chip in bf16).
_preset(ModelConfig(
    name="llama-3.2-1b",
    vocab_size=128256,
    hidden_size=2048,
    intermediate_size=8192,
    num_layers=16,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    rope_scaling=_LLAMA32_SCALING,
    tie_word_embeddings=True,
))

_preset(ModelConfig(
    name="llama-3.2-3b",
    vocab_size=128256,
    hidden_size=3072,
    intermediate_size=8192,
    num_layers=28,
    num_heads=24,
    num_kv_heads=8,
    head_dim=128,
    rope_scaling=_LLAMA32_SCALING,
    tie_word_embeddings=True,
))

# Flagship (BASELINE.json north star: disagg Llama-3.1-8B on v5e-16).
_preset(ModelConfig(
    name="llama-3.1-8b",
    vocab_size=128256,
    hidden_size=4096,
    intermediate_size=14336,
    num_layers=32,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    rope_scaling=_LLAMA31_SCALING,
))

_preset(ModelConfig(
    name="llama-3.1-70b",
    vocab_size=128256,
    hidden_size=8192,
    intermediate_size=28672,
    num_layers=80,
    num_heads=64,
    num_kv_heads=8,
    head_dim=128,
    rope_scaling=_LLAMA31_SCALING,
))

_preset(ModelConfig(
    name="qwen2.5-0.5b",
    vocab_size=151936,
    hidden_size=896,
    intermediate_size=4864,
    num_layers=24,
    num_heads=14,
    num_kv_heads=2,
    head_dim=64,
    rope_theta=1000000.0,
    rms_norm_eps=1e-6,
    max_position_embeddings=32768,
    tie_word_embeddings=True,
    attn_bias=True,
))

_preset(ModelConfig(
    name="mistral-7b",
    vocab_size=32000,
    hidden_size=4096,
    intermediate_size=14336,
    num_layers=32,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    rope_theta=1000000.0,
    max_position_embeddings=32768,
))

# Sparse MoE family (the reference serves Mixtral/DeepSeek-MoE through
# vLLM's fused-MoE kernels; here models/moe.py with the ep mesh axis).
TINY_MOE = _preset(ModelConfig(
    name="tiny-moe",
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    num_layers=2,
    num_heads=4,
    num_kv_heads=2,
    head_dim=16,
    rope_theta=10000.0,
    max_position_embeddings=2048,
    tie_word_embeddings=True,
    num_experts=4,
    num_experts_per_tok=2,
))

# Gemma-1 family: GeGLU MLP, sqrt(d)-scaled embeddings, (1+w) norms,
# wide head_dim (256) with kv=1 multi-query attention on the 2B.
_preset(ModelConfig(
    name="gemma-2b",
    vocab_size=256000,
    hidden_size=2048,
    intermediate_size=16384,
    num_layers=18,
    num_heads=8,
    num_kv_heads=1,
    head_dim=256,
    rope_theta=10000.0,
    rms_norm_eps=1e-6,
    max_position_embeddings=8192,
    tie_word_embeddings=True,
    hidden_act="gelu_pytorch_tanh",
    scale_embeddings=True,
    norm_weight_offset=1.0,
))

_preset(ModelConfig(
    name="mixtral-8x7b",
    vocab_size=32000,
    hidden_size=4096,
    intermediate_size=14336,
    num_layers=32,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    rope_theta=1000000.0,
    max_position_embeddings=32768,
    num_experts=8,
    num_experts_per_tok=2,
))


# DeepSeek-V2 family: latent attention over one cached row a token, 64
# softmax-routed experts top-6 (weights as they are) beside two shared
# ones, layer 0 dense; YaRN rope on a 64-wide slice shared by all heads.
_DEEPSEEK_V2_YARN = {
    "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
    "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
    "type": "yarn",
}

_preset(ModelConfig(
    name="deepseek-v2-lite",
    vocab_size=102400,
    hidden_size=2048,
    intermediate_size=10944,
    num_layers=27,
    num_heads=16,
    num_kv_heads=16,
    head_dim=192,
    rope_theta=10000,
    rms_norm_eps=1e-6,
    max_position_embeddings=163840,
    rope_scaling=_DEEPSEEK_V2_YARN,
    num_experts=64,
    num_experts_per_tok=6,
    moe_intermediate_size=1408,
    num_shared_experts=2,
    first_dense_layers=1,
    norm_topk_prob=False,
    routed_scaling_factor=1.0,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
))

# the same family at a size the CPU tests finish in seconds: 2 layers of
# which 1 dense, 8 experts top-2, 1 shared, latent rank 32, rope 16
TINY_MLA = _preset(ModelConfig(
    name="tiny-mla",
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    num_layers=2,
    num_heads=4,
    num_kv_heads=4,
    head_dim=48,
    rope_theta=10000.0,
    rms_norm_eps=1e-6,
    max_position_embeddings=2048,
    rope_scaling={**_DEEPSEEK_V2_YARN, "original_max_position_embeddings": 64},
    num_experts=8,
    num_experts_per_tok=2,
    moe_intermediate_size=32,
    num_shared_experts=1,
    first_dense_layers=1,
    norm_topk_prob=False,
    kv_lora_rank=32,
    qk_nope_head_dim=32,
    qk_rope_head_dim=16,
    v_head_dim=32,
))

# MiMo-V2 family: five window layers (128 tokens, a learned sink a head, 8
# KV heads) to one full-attention layer (4 KV heads), 192-wide keys over
# 128-wide values with rope on the first 64 dimensions, layer 0 dense, 256
# sigmoid-routed experts top-8 chosen with a correction bias, no shared one.
_MIMO_PERIOD = (FULL,) + (WINDOW,) * 4 + (FULL,) + (WINDOW,) * 5 + (FULL,)

_preset(ModelConfig(
    name="mimo-v2-flash",
    vocab_size=152576,
    hidden_size=4096,
    intermediate_size=16384,
    num_layers=48,
    num_heads=64,
    num_kv_heads=4,
    head_dim=192,
    rope_theta=5000000,
    rms_norm_eps=1e-5,
    max_position_embeddings=262144,
    num_experts=256,
    num_experts_per_tok=8,
    moe_intermediate_size=2048,
    first_dense_layers=1,
    norm_topk_prob=True,
    v_head_dim=128,
    layer_kinds=_MIMO_PERIOD[:6] + (_MIMO_PERIOD[6:] * 7),
    sliding_window=128,
    swa_num_kv_heads=8,
    swa_head_dim=192,
    swa_v_head_dim=128,
    swa_rope_theta=10000,
    rotary_dim=64,
    swa_sink=True,
    attn_value_scale=0.707,
    scoring_func="sigmoid",
    router_bias=True,
    experts_held=256,
))

# the same family at a size the CPU tests finish in seconds: the dense
# layer and two periods (window x2, full), window 16 over pages of 8, 8
# experts of which this "chip" holds 4, keys 24 wide (8 rotate) over
# values 16 wide
TINY_MIMO = _preset(ModelConfig(
    name="tiny-mimo",
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    num_layers=7,
    num_heads=4,
    num_kv_heads=1,
    head_dim=24,
    rope_theta=5000000,
    rms_norm_eps=1e-5,
    max_position_embeddings=2048,
    num_experts=8,
    num_experts_per_tok=2,
    moe_intermediate_size=32,
    first_dense_layers=1,
    norm_topk_prob=True,
    v_head_dim=16,
    layer_kinds=(FULL, WINDOW, WINDOW, FULL, WINDOW, WINDOW, FULL),
    sliding_window=16,
    swa_num_kv_heads=2,
    swa_head_dim=24,
    swa_v_head_dim=16,
    swa_rope_theta=10000,
    rotary_dim=8,
    swa_sink=True,
    attn_value_scale=0.707,
    scoring_func="sigmoid",
    router_bias=True,
    experts_held=4,
))


# Granite 4.0-H family: nine Mamba-2 layers (64 heads of 64, state 128, one
# group, convolution 4) to one attention layer (32 heads over 8 KV heads of
# 64, no position encoding, scores x 1/64), every layer followed by a
# SwiGLU of 8,192; embedding x 12, each block's output x 0.22 before the
# residual add, logits / 8; tied embedding; no routed experts.
_GRANITE_PERIOD = (MAMBA,) * 5 + (FULL,) + (MAMBA,) * 4

_preset(ModelConfig(
    name="granite-4.0-h-micro",
    vocab_size=100352,
    hidden_size=2048,
    intermediate_size=8192,
    num_layers=40,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    rope_theta=10000,
    rms_norm_eps=1e-5,
    max_position_embeddings=131072,
    tie_word_embeddings=True,
    layer_kinds=_GRANITE_PERIOD * 4,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attn_scale=0.015625,
    logits_scaling=8.0,
    use_rope=False,
    mamba_heads=64,
    mamba_head_dim=64,
    mamba_d_state=128,
    mamba_groups=1,
    mamba_d_conv=4,
    mamba_chunk=256,
))

# the same family at a size the CPU tests finish in seconds: mamba x2,
# attention, mamba; 4 heads of 8 with state 16, chunks of 8 tokens
TINY_GRANITE = _preset(ModelConfig(
    name="tiny-granite",
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    num_layers=4,
    num_heads=4,
    num_kv_heads=2,
    head_dim=16,
    rope_theta=10000,
    rms_norm_eps=1e-5,
    max_position_embeddings=2048,
    tie_word_embeddings=True,
    layer_kinds=(MAMBA, MAMBA, FULL, MAMBA),
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attn_scale=0.03125,
    logits_scaling=8.0,
    use_rope=False,
    mamba_heads=16,
    mamba_head_dim=8,
    mamba_d_state=16,
    mamba_groups=1,
    mamba_d_conv=4,
    mamba_chunk=8,
))


# Xing4.0 family: the deepseek_v3 layout (latent attention with low-rank
# queries at 32 heads, 64 sigmoid-routed experts top-4 chosen with a
# correction bias, renormalised, x 2, beside one shared expert, two
# leading dense layers, YaRN x 64 with mscale = mscale_all_dim = 1) around
# a residual of FOUR streams a token (models/mhc.py).
_XING_YARN = {
    "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
    "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
    "type": "yarn",
}

# the family's seeded weights (no key of the published config): a chosen
# expert weighs ~0.5 here (top-4 renormalised, x 2), so the seeded routed
# experts are scaled to a quarter, and a layer holds every expert, so a
# bias of N(0, 0.05) starves none
_XING_SEEDS = {"seed_expert_down_scale": 0.25, "seed_router_bias_std": 0.05}

_preset(ModelConfig(
    name="xing4.0-29b-a4b",
    vocab_size=131072,
    hidden_size=3584,
    intermediate_size=9216,
    num_layers=40,
    num_heads=32,
    num_kv_heads=32,
    head_dim=192,
    rope_theta=10000,
    rms_norm_eps=1e-6,
    max_position_embeddings=262144,
    rope_scaling=_XING_YARN,
    num_experts=64,
    num_experts_per_tok=4,
    moe_intermediate_size=1024,
    num_shared_experts=1,
    first_dense_layers=2,
    norm_topk_prob=True,
    routed_scaling_factor=2.0,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    scoring_func="sigmoid",
    router_bias=True,
    q_lora_rank=768,
    hc_mult=4,
    hc_sinkhorn_iters=20,
    hc_eps=1e-6,
    hc_res_clamp=30.0,
    **_XING_SEEDS,
))

# the same family at a size the CPU tests finish in seconds: 3 layers of
# which 1 dense, 8 experts top-2, 1 shared, latent rank 32, rope 16,
# query rank 24, four streams
TINY_XING = _preset(ModelConfig(
    name="tiny-xing",
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    num_layers=3,
    num_heads=4,
    num_kv_heads=4,
    head_dim=48,
    rope_theta=10000.0,
    rms_norm_eps=1e-6,
    max_position_embeddings=2048,
    rope_scaling={**_XING_YARN, "original_max_position_embeddings": 64},
    num_experts=8,
    num_experts_per_tok=2,
    moe_intermediate_size=32,
    num_shared_experts=1,
    first_dense_layers=1,
    norm_topk_prob=True,
    routed_scaling_factor=2.0,
    kv_lora_rank=32,
    qk_nope_head_dim=32,
    qk_rope_head_dim=16,
    v_head_dim=32,
    scoring_func="sigmoid",
    router_bias=True,
    q_lora_rank=24,
    hc_mult=4,
    **_XING_SEEDS,
))


# SDAR family: the 30B-A3B expert layout (32 query heads over 4 KV heads of
# 128 with an RMSNorm a head on queries and keys, rope base 1e6; 128
# softmax-routed experts top-8, renormalised, SwiGLU of 768, in every
# layer, no shared expert; untied embedding and head) generated by
# diffusion over blocks. The published config.json states no block keys
# (the catalog's `not_given`): the preset states the family's generation
# defaults, block 4, and of its transfer strategies the `sequential` one at
# 2 denoising steps (benchmark/configs/sdar-30b-a3b-l6.json `assumed`)
# the family's seeded weights (no key of the published config): at fan-in
# scale the seeded experts' outputs are ten times the attention's (a softmax
# over a thousand unrelated keys averages its values away), so what a
# comparison with a float32 reference read was top-8 SELECTIONS that bf16
# decides the other way (served 0.027-0.059 beside int8 weights at
# 0.053-0.071 and a causal line inside a block at 0.053-0.060: no limit
# lies between; PERF.md section 6, PR 47). The routed experts'
# down-projection is a twentieth of its fan-in scale: the attention, and with
# it the mask, is then what is judged (served 0.003-0.004, every control
# 2.7x and more above it), as `seed_expert_down_scale` 0.25 does for Xing4.0
_SDAR_SEEDS = {"seed_expert_down_scale": 0.05}

_preset(ModelConfig(
    name="sdar-30b-a3b",
    vocab_size=151936,
    hidden_size=2048,
    intermediate_size=6144,
    num_layers=48,
    num_heads=32,
    num_kv_heads=4,
    head_dim=128,
    rope_theta=1000000,
    rms_norm_eps=1e-6,
    max_position_embeddings=32768,
    num_experts=128,
    num_experts_per_tok=8,
    moe_intermediate_size=768,
    norm_topk_prob=True,
    qk_norm=True,
    block_length=4,
    denoising_steps=2,
    remasking_strategy="sequential",
    mask_token_id=151669,
    **_SDAR_SEEDS,
))

# the same family at a size the CPU tests finish in seconds: 2 layers, 8
# experts top-2, 4 heads over 2 KV heads of 16, blocks of 4 in 2 steps
TINY_SDAR = _preset(ModelConfig(
    name="tiny-sdar",
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    num_layers=2,
    num_heads=4,
    num_kv_heads=2,
    head_dim=16,
    rope_theta=1000000,
    rms_norm_eps=1e-6,
    max_position_embeddings=2048,
    num_experts=8,
    num_experts_per_tok=2,
    moe_intermediate_size=32,
    norm_topk_prob=True,
    qk_norm=True,
    block_length=4,
    denoising_steps=2,
    remasking_strategy="sequential",
    mask_token_id=255,
    **_SDAR_SEEDS,
))


def get_config(name: str) -> ModelConfig:
    if name not in PRESETS:
        raise KeyError(f"unknown model preset {name!r}; have {sorted(PRESETS)}")
    return PRESETS[name]
