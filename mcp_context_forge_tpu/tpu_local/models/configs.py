"""Model configurations."""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class LlamaConfig:
    """Geometry for the GQA+RoPE+SwiGLU decoder family.

    One trunk covers Llama-3, Mistral (v0.3+, which has no sliding window;
    window layers are :class:`AfmoeConfig`'s), Qwen2 and Gemma. Family
    knobs: ``attn_bias`` (Qwen2 q/k/v projection biases), ``tie_embeddings`` (Qwen2-0.5B, Llama-3.2-1B, Gemma — no
    ``lm_head.weight`` in the HF checkpoint), ``head_dim_override``
    (Gemma decouples head_dim from dim//n_heads: 2B uses 256-wide heads
    on a 2048 model dim), ``hidden_act`` (Gemma gates with tanh-approx
    GeLU instead of SiLU), ``embed_scale`` (Gemma multiplies embeddings
    by sqrt(dim)), and ``norm_plus_one`` (Gemma RMSNorm scales by
    ``1 + weight`` — HF stores the weight zero-centered)."""

    name: str
    vocab_size: int
    dim: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    ffn_hidden: int
    rope_theta: float = 500_000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 8192
    attn_bias: bool = False
    tie_embeddings: bool = False
    head_dim_override: int | None = None
    hidden_act: str = "silu"      # silu | gelu (tanh approximation)
    embed_scale: bool = False     # multiply embeddings by sqrt(dim)
    norm_plus_one: bool = False   # RMSNorm scales by (1 + weight)
    # MoE (Mixtral family): n_experts > 0 replaces the dense FFN with a
    # top-k routed expert FFN. Two drop-free serving formulations compute
    # the same per-token function, and models/llama.py: expert_path picks
    # one a STEP from what it can see — no caller tunes it:
    #   the row-block kernel (ops/grouped_moe.py: each token's chosen
    #   experts only, padding tokens and idle rows given no row, weight
    #   tiles DMA'd a block via scalar prefetch) on a mesh whose ``model``
    #   axis is one device, for a step wide enough that T·k >= E·moe_block
    #   (prefills, chunk rounds, wide history suffixes) at row-block
    #   moe_block, and for a narrower step whose padded rows
    #   T·k + E·b(T) are at most a quarter of the scan's E·T, at the
    #   row-block b(T) its width gives it (expert_block: the power of two
    #   that holds T·k / E pairs, from the activations' sublane tile up
    #   to moe_block) — a decode-width step over many small experts;
    #   the expert scan with gate masks (parallel/moe.py: E/k x the FLOPs,
    #   no gathers) for every other step — this family's decode and
    #   verify steps (8 x top-2: T·k alone is E·T / 4), where it is at
    #   its floor of one read of every expert's weights, a TP mesh, and
    #   callers that differentiate.
    # ``moe_impl`` names the family's widest choice: grouped_pallas (the
    # rule above), grouped (the same plan through XLA's gathered-weights
    # einsum, which materializes [NB, D, F]: small models and tests) or
    # dense (the scan always: what the tests hold the rule's other choices
    # to). The engine's ``moe_impl`` override exists for the CPU rehearsals
    # under tests/benchmark/ alone (ROADMAP Queue 3 ``unmeasured-options``).
    # moe_block is the kernel's WIDEST row-block and the width (in pairs
    # an expert) from which a step takes it whatever its rows.
    # parallel/moe.py's capacity dispatch stays the EP-training path.
    n_experts: int = 0
    moe_top_k: int = 2
    moe_impl: str = "grouped_pallas"
    moe_block: int = 128

    @property
    def head_dim(self) -> int:
        return self.head_dim_override or self.dim // self.n_heads

    @property
    def embed_multiplier(self) -> float:
        return float(self.dim) ** 0.5 if self.embed_scale else 1.0


@dataclass(frozen=True)
class DeepseekConfig:
    """Geometry for the latent-attention + shared/routed-expert family, with
    two optional parts: a learned sparse selector and a multi-token-prediction
    block (DeepSeek-V3 / V3.2 ``config.json`` keys in brackets).

    Attention goes through low-rank latents: the cache holds one
    ``kv_lora_rank + qk_rope_head_dim`` vector a token a layer, shared by all
    heads. With ``index_topk`` > 0 a selector keeps that many positions a
    query (and the cache a second pool, the selector's ``index_head_dim``
    key); with 0 there are no selector weights and no second pool, and a query
    attends to everything it may see. ``n_mtp_blocks`` [num_nextn_predict_layers]
    is 0 or 1: one more decoder layer of the expert kind (with its own latent
    entries, cache layer ``n_layers``) that drafts the token after next from
    the last layer's hidden state (``models/deepseek.py: draft_step``). Rotary
    frequencies are YaRN's where ``max_seq_len`` passes ``rope_original_max``
    and plain otherwise (a model without rope scaling sets the two equal). The first
    ``n_dense_layers`` [first_k_dense_replace] layers carry a dense SwiGLU of
    ``ffn_hidden`` [intermediate_size]; the rest route over ``n_routed_experts``
    (sigmoid scores, bias-corrected group-limited top-k) beside
    ``n_shared_experts`` always-on ones of ``moe_ffn_hidden``
    [moe_intermediate_size]. ``experts_held`` is the half-open range of routed
    experts THIS engine computes: routing is over all of them, a pair that
    lands outside the range adds nothing here (its chip of an expert-parallel
    deployment would). ``moe_impl`` as in :class:`LlamaConfig`; ``moe_block``
    is this family's one row-block, and by its own rule
    (``models/deepseek.py: expert_path``) steps narrower than ``moe_block``
    tokens take the expert scan."""

    name: str
    vocab_size: int
    dim: int
    n_layers: int
    n_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    ffn_hidden: int
    moe_ffn_hidden: int
    n_routed_experts: int
    experts_held: tuple[int, int]
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    n_mtp_blocks: int = 0
    n_dense_layers: int = 1
    n_shared_experts: int = 1
    moe_top_k: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    rope_theta: float = 10_000.0
    rope_factor: float = 40.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 1.0
    norm_eps: float = 1e-6
    max_seq_len: int = 163_840
    moe_impl: str = "grouped_pallas"
    moe_block: int = 128

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def latent_dim(self) -> int:
        """What the cache holds a token a layer for attention: c || k_r."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def has_selector(self) -> bool:
        return self.index_topk > 0

    @property
    def n_cache_layers(self) -> int:
        """Layers that hold latent entries: the model's and the block's."""
        return self.n_layers + self.n_mtp_blocks

    def ffn_kind(self, layer: int) -> str:
        return "dense" if layer < self.n_dense_layers else "experts"


@dataclass(frozen=True)
class OlmoHybridConfig:
    """Geometry for the hybrid family whose layers differ in their MIXER
    (Olmo-Hybrid ``config.json`` keys in brackets): layer ``i`` is a gated
    delta-rule linear-attention layer unless ``i % full_attention_interval ==
    full_attention_interval - 1`` [layer_types], when it is full softmax
    attention over ``n_kv_heads`` heads of ``head_dim`` with QK-norm and no
    rotary embedding. A linear layer holds ``linear_n_heads``
    [linear_num_key_heads = linear_num_value_heads] heads of a
    ``linear_key_dim`` x ``linear_value_dim`` float32 state a SEQUENCE and the
    last ``conv_kernel - 1`` pre-convolution inputs; it keeps nothing a token.
    Both kinds: post-norm residual blocks (``x + norm(f(x))``) and SwiGLU."""

    name: str
    vocab_size: int
    dim: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    ffn_hidden: int
    linear_n_heads: int
    linear_key_dim: int
    linear_value_dim: int
    conv_kernel: int = 4
    full_attention_interval: int = 4
    allow_neg_eigval: bool = True   # beta = 2 * sigmoid(b)
    norm_eps: float = 1e-6
    max_seq_len: int = 65_536

    def mixer_kind(self, layer: int) -> str:
        full = layer % self.full_attention_interval \
            == self.full_attention_interval - 1
        return "full_attention" if full else "linear_attention"

    def layers_of(self, kind: str) -> tuple[int, ...]:
        return tuple(i for i in range(self.n_layers)
                     if self.mixer_kind(i) == kind)

    @property
    def kv_pool_heads(self) -> int:
        """kv heads a K/V PAGE holds: the model's, rounded up to whole
        16-row sublane tiles once they pass one (30 -> 32). A token's heads
        are the page's sublane dim; with 30 of them the TPU's default layout
        of the pool is no longer row-major (it transposes page and heads to
        save the 2 padding rows), and every kernel call over the pool then
        copies the whole pool into the layout it needs. The padding heads
        are zeros that no query reads."""
        n = self.n_kv_heads
        return n if n <= 16 else -(-n // 16) * 16

    @property
    def conv_dim(self) -> int:
        """Channels the causal convolution runs over: q, k and v."""
        return self.linear_n_heads * (2 * self.linear_key_dim
                                      + self.linear_value_dim)


@dataclass(frozen=True)
class SdarConfig:
    """Geometry and generation settings of the block-diffusion family (SDAR
    ``config.json`` keys in brackets): the Qwen3-MoE trunk (GQA with a
    per-head RMSNorm on q and k before the rotation, every layer an expert
    layer of ``n_experts`` [num_experts] experts of ``ffn_hidden``
    [moe_intermediate_size], top ``moe_top_k`` [num_experts_per_tok],
    softmax over all then renormalised over the chosen) under a BLOCK-CAUSAL
    mask: key j is visible to query i iff ``p_j // block_length <= p_i //
    block_length``. ``logits_i`` are the distribution of token i itself, so a
    prompt yields no token and generation fills blocks of ``block_length``
    positions that start as ``mask_token_id``: at most ``denoising_steps``
    passes, each committing the masked positions whose confidence passes
    ``confidence_threshold`` or, failing enough of those, the most confident
    ones (``models/sdar.py``). ``moe_impl``/``moe_block`` as in
    :class:`LlamaConfig`: with 128 x top-8 a block step of 128 tokens is a
    narrow step the rule sends to the row-block kernel at 16 rows."""

    name: str
    vocab_size: int
    dim: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    ffn_hidden: int
    n_experts: int
    moe_top_k: int
    block_length: int = 4
    denoising_steps: int = 4
    confidence_threshold: float = 0.9
    mask_token_id: int = 151_669
    rope_theta: float = 1_000_000.0
    norm_eps: float = 1e-6
    max_seq_len: int = 32_768
    hidden_act: str = "silu"
    moe_impl: str = "grouped_pallas"
    moe_block: int = 128


@dataclass(frozen=True)
class AfmoeConfig:
    """Geometry of the family whose layers differ in BOTH parts (``afmoe``
    ``config.json`` keys in brackets). The mixer [layer_types]: layer ``i`` is
    a WINDOW layer (rotary, a query sees the ``sliding_window`` newest keys,
    its own included) unless ``i % global_attn_every == global_attn_every -
    1``, when it is a FULL layer (no rotation, every earlier key). The FFN:
    the leading ``n_dense_layers`` [num_dense_layers] are dense SwiGLU of
    ``ffn_hidden`` [intermediate_size], the rest route over ``n_experts`` of
    ``moe_ffn_hidden`` [moe_intermediate_size] (sigmoid scores plus a
    correction bias choose ``moe_top_k``; the chosen scores, normalised and
    times ``routed_scaling_factor`` [route_scale], weigh them) beside one
    shared expert of ``n_shared_experts * moe_ffn_hidden``. Both kinds: GQA
    with a per-head RMSNorm on q and k, an output gate ``sigmoid(x W_g)`` on
    the attention's heads, four norms a layer (``x + norm(f(norm(x)))``), the
    embedding times ``sqrt(dim)`` [mup_enabled], an untied head.

    ``ring_slack``: tokens a sequence's ring of window K/V holds beyond the
    window: the widest step that writes before it attends (a chunk round)
    and one page, so that a step's queries find every key of their windows
    (``kv/paged_cache.py``). The family refuses an engine whose largest
    prefill bucket and page do not fit it. ``n_group`` / ``topk_group`` are 1
    (group limiting is the identity); ``moe_impl`` / ``moe_block`` as in
    :class:`LlamaConfig`, but which formulation a narrow step takes is the
    family's own rule (``models/afmoe.py: expert_path``)."""

    name: str
    vocab_size: int
    dim: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    ffn_hidden: int
    moe_ffn_hidden: int
    n_experts: int
    moe_top_k: int
    sliding_window: int
    global_attn_every: int = 4
    n_dense_layers: int = 2
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.826
    n_group: int = 1
    topk_group: int = 1
    ring_slack: int = 1152
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 131_072
    hidden_act: str = "silu"
    moe_impl: str = "grouped_pallas"
    moe_block: int = 128

    def mixer_kind(self, layer: int) -> str:
        full = layer % self.global_attn_every == self.global_attn_every - 1
        return "full" if full else "window"

    def ffn_kind(self, layer: int) -> str:
        return "dense" if layer < self.n_dense_layers else "experts"

    def layers_of(self, mixer: str) -> tuple[int, ...]:
        return tuple(i for i in range(self.n_layers)
                     if self.mixer_kind(i) == mixer)

    @property
    def n_held(self) -> int:
        """Routed experts this engine computes: all of them (the name a
        family with a share gives: :class:`SolarOpen2Config`)."""
        return self.n_experts

    @property
    def ring_tokens(self) -> int:
        """Tokens of K and of V a window layer keeps a sequence."""
        return self.sliding_window + self.ring_slack

    @property
    def embed_multiplier(self) -> float:
        return float(self.dim) ** 0.5


@dataclass(frozen=True)
class SolarOpen2Config:
    """Geometry of the hybrid family whose layers differ in their MIXER and
    all route (``solar_open2`` ``config.json`` keys in brackets). Layer ``i``
    is a gated NoPE GQA layer if ``i`` is in ``gqa_layers`` [gqa_layers]
    (``n_heads`` query and ``n_kv_heads`` kv heads of ``head_dim``, no rotary
    embedding [use_rope false], an elementwise output gate [use_gqa_gate])
    and a Kimi-Delta-Attention layer otherwise: the gated delta rule with a
    decay a head AND a key channel, ``linear_n_heads`` [linear_attn_config
    .num_heads] heads of a ``linear_key_dim`` x ``linear_value_dim`` [both
    .head_dim] float32 state a SEQUENCE, a causal convolution of
    ``conv_kernel`` [.short_conv_kernel_size] taps, the decay and the output
    gate through low-rank pairs of ``gate_rank`` [kda_use_full_proj false],
    ``beta = 2 sigmoid(.)`` [kda_allow_neg_eigval]. The field names of the
    linear part are :class:`OlmoHybridConfig`'s: the cache pools, the state
    rows and the mixer's plumbing are shared (``kv/paged_cache.py``,
    ``models/olmo_hybrid.py``). EVERY layer's FFN routes [first_k_dense_replace
    0] over ``n_experts`` [n_routed_experts] of ``moe_ffn_hidden``
    [moe_intermediate_size] (sigmoid scores plus a correction bias choose
    ``moe_top_k``; the chosen scores, normalised [norm_topk_prob] and times
    ``routed_scaling_factor``, weigh them) beside ``n_shared_experts``.
    ``experts_held`` is the half-open range of routed experts THIS engine
    computes, as :class:`DeepseekConfig`'s: routing is over all
    ``n_experts``, a pair outside the range adds nothing here. Pre-norm
    residual blocks, an untied head."""

    name: str
    vocab_size: int
    dim: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    moe_ffn_hidden: int
    n_experts: int
    experts_held: tuple[int, int]
    moe_top_k: int
    linear_n_heads: int
    linear_key_dim: int
    linear_value_dim: int
    gate_rank: int
    gqa_layers: tuple[int, ...]
    conv_kernel: int = 4
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    n_group: int = 1
    topk_group: int = 1
    allow_neg_eigval: bool = True   # beta = 2 * sigmoid(b)
    norm_eps: float = 1e-5
    max_seq_len: int = 1_048_576
    hidden_act: str = "silu"
    moe_impl: str = "grouped_pallas"
    moe_block: int = 128

    def mixer_kind(self, layer: int) -> str:
        return ("full_attention" if layer in self.gqa_layers
                else "linear_attention")

    def layers_of(self, kind: str) -> tuple[int, ...]:
        return tuple(i for i in range(self.n_layers)
                     if self.mixer_kind(i) == kind)

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def kv_pool_heads(self) -> int:
        """kv heads a K/V page holds (:class:`OlmoHybridConfig`'s name): the
        model's, 8 being whole sublane rows already."""
        return self.n_kv_heads

    @property
    def conv_dim(self) -> int:
        """Channels the causal convolution runs over: q, k and v."""
        return self.linear_n_heads * (2 * self.linear_key_dim
                                      + self.linear_value_dim)


@dataclass(frozen=True)
class GraniteHybridConfig:
    """Geometry of the hybrid family whose layers are Mamba-2 state-space
    mixers or un-rotated GQA by a LIST (``granitemoehybrid`` ``config.json``
    keys in brackets). Layer ``i``'s mixer is ``layer_types[i]``
    [layer_types]: ``mamba`` | ``attention``. An attention layer has
    ``n_heads`` query and ``n_kv_heads`` kv heads of ``head_dim``, no rotary
    embedding [position_embedding_type nope], no bias, and softmax at
    ``attention_multiplier`` (NOT ``head_dim^-0.5``). A Mamba layer has
    ``mamba_n_heads`` heads of ``mamba_head_dim`` channels [mamba_n_heads,
    mamba_d_head; their product is mamba_expand x hidden_size], ONE group of
    ``mamba_d_state`` B and C channels that all heads share [mamba_n_groups
    1], a causal depthwise convolution with bias of ``conv_kernel`` taps
    [mamba_d_conv, mamba_conv_bias] over x, B and C together, and keeps a
    ``mamba_d_state`` x ``mamba_n_heads * mamba_head_dim`` float32 state a
    SEQUENCE beside the last ``conv_kernel - 1`` pre-convolution inputs; it
    keeps nothing a token. Every layer: a pre-norm residual block whose two
    sublayers add ``residual_multiplier`` times their output, a dense SwiGLU
    MLP of ``ffn_hidden`` [shared_intermediate_size; num_local_experts 0].
    The embedding is times ``embedding_multiplier``, the logits are divided
    by ``logits_scaling``, and the head is the embedding [tie_word_embeddings].
    The field names the cache and the state-row plumbing read are
    :class:`OlmoHybridConfig`'s (``linear_*``, ``conv_dim``, ``layers_of``)."""

    name: str
    vocab_size: int
    dim: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    ffn_hidden: int
    mamba_n_heads: int
    mamba_head_dim: int
    mamba_d_state: int
    layer_types: tuple[str, ...]
    conv_kernel: int = 4
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 1.0
    logits_scaling: float = 1.0
    norm_eps: float = 1e-5
    max_seq_len: int = 131_072
    hidden_act: str = "silu"

    def __post_init__(self) -> None:
        bad = set(self.layer_types) - {"mamba", "attention"}
        if bad or len(self.layer_types) != self.n_layers:
            raise ValueError(f"{self.name}: layer_types must name {self.n_layers} "
                             f"mixers, each mamba or attention; got {self.layer_types}")

    def mixer_kind(self, layer: int) -> str:
        """``linear_attention`` (a Mamba-2 layer: a state a sequence) |
        ``full_attention``, the names ``kv/paged_cache.py: kv_pools`` counts
        layers by."""
        return ("full_attention" if self.layer_types[layer] == "attention"
                else "linear_attention")

    def layers_of(self, kind: str) -> tuple[int, ...]:
        return tuple(i for i in range(self.n_layers)
                     if self.mixer_kind(i) == kind)

    @property
    def kv_pool_heads(self) -> int:
        """kv heads a K/V page holds (:class:`OlmoHybridConfig`'s name)."""
        return self.n_kv_heads

    @property
    def kv_head_dim(self) -> int:
        """Lanes a K or V head is STORED in, and attended over: whole
        128-lane tiles, a zero tail past ``head_dim`` (64 -> 128). A pool whose
        minor dimension is half a tile is kept compressed by the chip's
        compiler, which then copies it whole around every write and read (read
        on the chip: ``PERF.md`` section 6, PR 53), and the flash and paged
        kernels take whole tiles only. The scores and the first ``head_dim``
        output lanes are what the narrow head's would be."""
        return -(-self.head_dim // 128) * 128

    @property
    def mamba_inner(self) -> int:
        """Channels of x, z and the state's lane axis: heads x head_dim."""
        return self.mamba_n_heads * self.mamba_head_dim

    # the state pool's geometry under the names the cache reads
    @property
    def linear_n_heads(self) -> int:
        return self.mamba_n_heads

    @property
    def linear_key_dim(self) -> int:
        return self.mamba_d_state

    @property
    def linear_value_dim(self) -> int:
        return self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the causal convolution runs over: x, B and C (one group)."""
        return self.mamba_inner + 2 * self.mamba_d_state


MODEL_CONFIGS: dict[str, LlamaConfig | DeepseekConfig | OlmoHybridConfig
                    | SdarConfig | AfmoeConfig | SolarOpen2Config
                    | GraniteHybridConfig] = {
    # Llama-3-8B geometry (the BASELINE.json flagship)
    "llama3-8b": LlamaConfig(
        name="llama3-8b", vocab_size=128_256, dim=4096, n_layers=32,
        n_heads=32, n_kv_heads=8, ffn_hidden=14_336, rope_theta=500_000.0,
        max_seq_len=8192),
    # ~1B-class for single-chip smoke runs (Llama-3.2-1B geometry; HF ships
    # it with tied embeddings and no lm_head.weight — checkpoints saved
    # before tie_embeddings landed must be re-exported under this name)
    "llama3-1b": LlamaConfig(
        name="llama3-1b", vocab_size=128_256, dim=2048, n_layers=16,
        n_heads=32, n_kv_heads=8, ffn_hidden=8192, max_seq_len=8192,
        tie_embeddings=True),
    # Mistral-7B v0.3 (no sliding window since v0.3)
    "mistral-7b": LlamaConfig(
        name="mistral-7b", vocab_size=32_768, dim=4096, n_layers=32,
        n_heads=32, n_kv_heads=8, ffn_hidden=14_336, rope_theta=1_000_000.0,
        max_seq_len=32_768),
    # Qwen2-7B (QKV biases)
    "qwen2-7b": LlamaConfig(
        name="qwen2-7b", vocab_size=152_064, dim=3584, n_layers=28,
        n_heads=28, n_kv_heads=4, ffn_hidden=18_944, rope_theta=1_000_000.0,
        norm_eps=1e-6, max_seq_len=32_768, attn_bias=True),
    # Mixtral-8x7B: Mistral trunk + 8-expert top-2 MoE FFN
    "mixtral-8x7b": LlamaConfig(
        name="mixtral-8x7b", vocab_size=32_000, dim=4096, n_layers=32,
        n_heads=32, n_kv_heads=8, ffn_hidden=14_336,
        rope_theta=1_000_000.0, max_seq_len=32_768, n_experts=8,
        moe_top_k=2),
    # Gemma-2B: MQA (1 kv head), 256-wide heads decoupled from dim,
    # GeGLU, sqrt(dim)-scaled embeddings, (1+w) RMSNorm, tied head
    "gemma-2b": LlamaConfig(
        name="gemma-2b", vocab_size=256_000, dim=2048, n_layers=18,
        n_heads=8, n_kv_heads=1, ffn_hidden=16_384, rope_theta=10_000.0,
        norm_eps=1e-6, max_seq_len=8192, tie_embeddings=True,
        head_dim_override=256, hidden_act="gelu", embed_scale=True,
        norm_plus_one=True),
    # Qwen2-0.5B (QKV biases + tied embeddings)
    "qwen2-0.5b": LlamaConfig(
        name="qwen2-0.5b", vocab_size=151_936, dim=896, n_layers=24,
        n_heads=14, n_kv_heads=2, ffn_hidden=4864, rope_theta=1_000_000.0,
        norm_eps=1e-6, max_seq_len=32_768, attn_bias=True,
        tie_embeddings=True),
    # tiny Qwen2-style config exercising both family knobs in CI
    "qwen2-tiny": LlamaConfig(
        name="qwen2-tiny", vocab_size=512, dim=256, n_layers=4,
        n_heads=8, n_kv_heads=4, ffn_hidden=688, max_seq_len=2048,
        attn_bias=True, tie_embeddings=True),
    # tiny configs for CI / CPU mesh (byte-level tokenizer vocab)
    "llama3-tiny": LlamaConfig(
        name="llama3-tiny", vocab_size=512, dim=256, n_layers=4,
        n_heads=8, n_kv_heads=4, ffn_hidden=688, max_seq_len=2048),
    "llama3-test": LlamaConfig(
        name="llama3-test", vocab_size=512, dim=64, n_layers=2,
        n_heads=4, n_kv_heads=2, ffn_hidden=128, max_seq_len=512),
    # gemma geometry at CI scale: every family knob exercised (MQA,
    # decoupled 32-wide heads on a 64 model dim, GeGLU, scaled embeds,
    # (1+w) norms, tied head)
    # mixtral geometry at CI scale (4 experts, top-2)
    "mixtral-test": LlamaConfig(
        name="mixtral-test", vocab_size=512, dim=64, n_layers=2,
        n_heads=4, n_kv_heads=2, ffn_hidden=96, max_seq_len=512,
        n_experts=4, moe_top_k=2),
    "gemma-test": LlamaConfig(
        name="gemma-test", vocab_size=512, dim=64, n_layers=2,
        n_heads=4, n_kv_heads=1, ffn_hidden=128, rope_theta=10_000.0,
        norm_eps=1e-6, max_seq_len=512, tie_embeddings=True,
        head_dim_override=32, hidden_act="gelu", embed_scale=True,
        norm_plus_one=True),
    # latent attention + sparse selector + shared/routed experts at CI
    # scale: 3 layers (1 dense), 16 experts in 4 groups of which 2 are kept,
    # top-4, 4 held here; index_topk 8 so that the selected set is a strict
    # subset at test lengths; 16 selector heads so that no two index scores
    # tie (few heads leave rows of exact zeros behind the ReLU); the grouped
    # experts through XLA (the kernel interprets slowly off the chip)
    "deepseek-test": DeepseekConfig(
        name="deepseek-test", vocab_size=512, dim=64, n_layers=3, n_heads=4,
        q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=16, v_head_dim=16, index_n_heads=16,
        index_head_dim=16, index_topk=8, ffn_hidden=128, moe_ffn_hidden=32,
        n_routed_experts=16, experts_held=(0, 4), n_dense_layers=1,
        n_shared_experts=1, moe_top_k=4, n_group=4, topk_group=2,
        rope_factor=4.0, rope_original_max=64, max_seq_len=512,
        moe_impl="grouped", moe_block=8),
    # the latent family WITHOUT a selector and WITH the multi-token-prediction
    # block (spec_decode drafts on the device): one router group, plain
    # rotary frequencies (no scaling: rope_original_max = max_seq_len)
    "deepseek-mtp-test": DeepseekConfig(
        name="deepseek-mtp-test", vocab_size=512, dim=64, n_layers=3,
        n_heads=4, q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=16, v_head_dim=16, ffn_hidden=128, moe_ffn_hidden=32,
        n_routed_experts=16, experts_held=(0, 4), n_mtp_blocks=1,
        n_dense_layers=1, n_shared_experts=1, moe_top_k=4, n_group=1,
        topk_group=1, rope_original_max=512, max_seq_len=512,
        moe_impl="grouped", moe_block=8),
    # the hybrid family at CI scale: two periods of (3 gated delta-rule layers,
    # 1 full-attention layer); 4 linear heads of a 16 x 32 state
    "olmo-hybrid-test": OlmoHybridConfig(
        name="olmo-hybrid-test", vocab_size=512, dim=64, n_layers=8,
        n_heads=4, n_kv_heads=4, head_dim=16, ffn_hidden=128,
        linear_n_heads=4, linear_key_dim=16, linear_value_dim=32,
        max_seq_len=512),
    # the block-diffusion family at CI scale: 8 experts top-2, blocks of 4
    # positions in at most 4 passes; the mask token is an id of the tiny
    # vocabulary; the grouped experts through XLA as deepseek-test
    "sdar-test": SdarConfig(
        name="sdar-test", vocab_size=512, dim=64, n_layers=2, n_heads=4,
        n_kv_heads=2, head_dim=16, ffn_hidden=32, n_experts=8, moe_top_k=2,
        mask_token_id=511, max_seq_len=512, moe_impl="grouped",
        moe_block=8),
    # the window / full family at CI scale: two periods of (3 window layers,
    # 1 full layer), 1 dense layer then expert layers of 8 experts top-2 and a
    # shared one; a window of 4 pages of 8 and a ring of 7 (slack: a chunk of
    # 16 and a page), so that a test's ring wraps; the grouped experts through
    # XLA as deepseek-test
    "afmoe-test": AfmoeConfig(
        name="afmoe-test", vocab_size=512, dim=64, n_layers=8, n_heads=4,
        n_kv_heads=2, head_dim=16, ffn_hidden=128, moe_ffn_hidden=32,
        n_experts=8, moe_top_k=2, sliding_window=32, n_dense_layers=1,
        ring_slack=24, max_seq_len=512, moe_impl="grouped", moe_block=8),
    # the KDA / gated-GQA expert family at CI scale: two periods of (1 GQA
    # layer, 3 channel-decay delta-rule layers of 4 heads of a 16 x 16 state),
    # every layer routing top-4 over 16 experts of which 4 are held (a range
    # that does not start at 0) beside a shared one; the grouped experts
    # through XLA as deepseek-test
    "solar-open2-test": SolarOpen2Config(
        name="solar-open2-test", vocab_size=512, dim=64, n_layers=8,
        n_heads=4, n_kv_heads=2, head_dim=16, moe_ffn_hidden=32,
        n_experts=16, experts_held=(4, 8), moe_top_k=4, linear_n_heads=4,
        linear_key_dim=16, linear_value_dim=16, gate_rank=16,
        gqa_layers=(0, 4), max_seq_len=512, moe_impl="grouped", moe_block=8),
    # the Mamba-2 / un-rotated GQA family at CI scale, with the published
    # RATIOS: one group of B and C, heads x head_dim = 2 x dim, attention at an
    # interior position of each period, a tied head, the four multipliers
    "granite-hybrid-test": GraniteHybridConfig(
        name="granite-hybrid-test", vocab_size=512, dim=64, n_layers=8,
        n_heads=4, n_kv_heads=2, head_dim=16, ffn_hidden=128,
        mamba_n_heads=8, mamba_head_dim=16, mamba_d_state=16,
        layer_types=("mamba", "mamba", "attention", "mamba") * 2,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=0.0625, logits_scaling=8.0,
        max_seq_len=512),
}


@dataclass(frozen=True)
class EncoderConfig:
    name: str
    vocab_size: int
    dim: int
    n_layers: int
    n_heads: int
    ffn_hidden: int
    max_seq_len: int = 512
    n_classes: int = 2  # moderation head: [safe, harmful]
    norm_eps: float = 1e-5


ENCODER_CONFIGS: dict[str, EncoderConfig] = {
    # MiniLM-class (the reference BASELINE.json embed model gloss)
    "encoder-mini": EncoderConfig(
        name="encoder-mini", vocab_size=30_522, dim=384, n_layers=6,
        n_heads=12, ffn_hidden=1536),
    "encoder-tiny": EncoderConfig(
        name="encoder-tiny", vocab_size=512, dim=128, n_layers=2,
        n_heads=4, ffn_hidden=256),
}
