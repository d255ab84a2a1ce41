"""Architecture / shape / federated configuration dataclasses.

Every assigned architecture is described by an :class:`ArchConfig` (exact
numbers from the assignment, source cited in each ``configs/<id>.py``) plus a
``smoke()`` reduced variant (2 layers, d_model<=512, <=4 experts) used by the
CPU smoke tests.  The four assigned input shapes live in ``SHAPES``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Layer kinds
# ---------------------------------------------------------------------------
ATTN = "attn"            # GQA self-attention
MAMBA = "mamba"          # Mamba2 SSD block
CROSS = "cross"          # cross-attention (VLM image layers / enc-dec)


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    num_shared: int = 0                 # always-on shared experts (deepseek)
    d_expert: Optional[int] = None      # per-expert FFN width (None -> d_ff)
    every: int = 1                      # MoE MLP every `every`-th layer
    aux_loss_coef: float = 0.01         # router load-balance aux loss
    capacity_factor: float = 1.25       # expert capacity = K*gs/E * this
    group_size: int = 4096              # tokens per dispatch group


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """DeepSeek-V2 multi-head latent attention."""
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = None   # None -> dense q projection (v2-lite)
    rope_head_dim: int = 64             # decoupled RoPE key dimension


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """Mamba2 SSD block hyper-parameters."""
    d_state: int = 128
    d_head: int = 64                    # P in SSD; heads = d_inner // d_head
    expand: int = 2                     # d_inner = expand * d_model
    chunk: int = 256                    # SSD chunk length
    d_conv: int = 4                     # depthwise conv width
    n_groups: int = 1                   # B/C projection groups (per-group, not per-head)


@dataclasses.dataclass(frozen=True)
class EncoderConfig:
    """Stub-frontend encoder (audio/vision).  The conv/mel (whisper) or ViT
    (VLM) frontend is NOT implemented (per assignment carve-out); inputs are
    precomputed frame/patch embeddings of shape (batch, enc_len, enc_dim)."""
    enc_layers: int
    enc_len: int                        # number of frames / image tokens
    enc_dim: int                        # embedding dim delivered by the stub
    enc_heads: int = 16
    enc_ff: int = 0                     # 0 -> 4*enc_dim


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                         # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int                      # query heads (0 for attn-free)
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                   # 0 -> d_model // num_heads
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    encoder: Optional[EncoderConfig] = None
    # hybrid interleave: within each group of `attn_period` layers, one is
    # attention and the rest are `MAMBA` (jamba: 1:7 -> attn_period=8).
    attn_period: int = 1                # 1 => every layer is attention
    cross_every: int = 0                # >0: every k-th layer is cross-attn (vlm)
    sliding_window: int = 0             # >0: sliding-window attention variant
    dtype: str = "bfloat16"
    source: str = ""                    # citation for the exact numbers

    # ---- derived -----------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // max(self.num_heads, 1)

    def layer_kinds(self) -> Tuple[str, ...]:
        """Sequence of layer kinds, length == num_layers."""
        kinds = []
        for i in range(self.num_layers):
            if self.cross_every and (i % self.cross_every == self.cross_every - 1):
                kinds.append(CROSS)
            elif self.attn_period > 1:
                # jamba-style: attention once per period (in the middle),
                # mamba elsewhere.
                kinds.append(ATTN if i % self.attn_period == self.attn_period // 2
                             else MAMBA)
            elif self.family == "ssm":
                kinds.append(MAMBA)
            else:
                kinds.append(ATTN)
        return tuple(kinds)

    def param_count(self) -> int:
        """Analytic parameter count (embeddings + per-layer blocks)."""
        d, v = self.d_model, self.vocab_size
        hd = self.resolved_head_dim
        total = v * d                                    # token embedding
        if not self.tie_embeddings:
            total += v * d                               # lm head
        kinds = self.layer_kinds()
        for i, kind in enumerate(kinds):
            total += 2 * d                               # 2 RMSNorm scales
            if kind == MAMBA:
                s = self.ssm or SSMConfig()
                d_in = s.expand * d
                heads = d_in // s.d_head
                # in_proj -> [z, x, B, C, dt]; B/C are per-group
                total += d * (2 * d_in + 2 * s.n_groups * s.d_state + heads)
                total += (d_in + 2 * s.n_groups * s.d_state) * s.d_conv  # conv over x,B,C
                total += 2 * heads                       # A, D per head
                total += d_in * d                        # out_proj
            elif kind in (ATTN, CROSS):
                if self.mla is not None:
                    m = self.mla
                    q_dim = self.num_heads * (hd + m.rope_head_dim)
                    total += d * (m.kv_lora_rank + m.rope_head_dim)       # kv down
                    total += m.kv_lora_rank * self.num_heads * 2 * hd     # kv up
                    total += d * q_dim                                    # q proj
                    total += self.num_heads * hd * d                      # o proj
                else:
                    total += d * self.num_heads * hd                      # q
                    total += 2 * d * self.num_kv_heads * hd               # k,v
                    total += self.num_heads * hd * d                      # o
            # MLP / MoE (mamba blocks in jamba also carry an MLP per layer)
            total += self._mlp_params(i)
        if self.encoder is not None:
            e = self.encoder
            eff = e.enc_ff or 4 * e.enc_dim
            per = 4 * e.enc_dim * e.enc_dim + 3 * e.enc_dim * eff + 2 * e.enc_dim
            total += e.enc_layers * per
        return total

    def _mlp_params(self, layer_idx: int) -> int:
        d = self.d_model
        if self.moe is not None and (layer_idx % self.moe.every == self.moe.every - 1):
            m = self.moe
            de = m.d_expert or self.d_ff
            routed = m.num_experts * 3 * d * de          # swiglu experts
            shared = m.num_shared * 3 * d * de
            router = d * m.num_experts
            return routed + shared + router
        if self.d_ff == 0:
            return 0                                     # attn-free pure SSM
        return 3 * d * self.d_ff                         # swiglu dense

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: top_k + shared experts only)."""
        if self.moe is None:
            return self.param_count()
        m = self.moe
        de = m.d_expert or self.d_ff
        n_moe_layers = sum(1 for i in range(self.num_layers)
                           if i % m.every == m.every - 1)
        inactive = n_moe_layers * (m.num_experts - m.top_k) * 3 * self.d_model * de
        return self.param_count() - inactive


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                            # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k":    ShapeConfig("train_4k",    4_096,   256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768,   32, "prefill"),
    "decode_32k":  ShapeConfig("decode_32k",  32_768,  128, "decode"),
    "long_500k":   ShapeConfig("long_500k",  524_288,    1, "decode"),
}


# ---------------------------------------------------------------------------
# Federated configuration (the paper's knobs)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class FedConfig:
    """Paper notation: B local batch, E local epochs, C client fraction.

    algorithm: any name in the ClientAlgorithm registry
    (``repro.core.algorithms``).  Built-ins:
      'fedavg'   — FedAvg local SGD, delta aggregation (biased)   [paper baseline]
      'uga'      — keep-trace GD + gradient evaluation (unbiased) [paper §3.1]
      'fedprox'  — FedAvg + proximal term mu/2 ||w - w_t||^2      [paper baseline]
      'fednova'  — tau_k-normalized delta averaging               [Wang et al. 2020]
    New algorithms register via ``repro.core.algorithms.register_algorithm``
    (one file, no core edits) and are accepted here by name.
    meta: FedMeta server meta-update after aggregation            [paper §3.2]
    share: FedShare — inject globally shared samples into client batches.
    """
    algorithm: str = "uga"
    meta: bool = True
    share: bool = False
    cohort: int = 16                    # clients per round (= C*K)
    local_steps: int = 2                # local steps per epoch; UGA: last = grad eval
    local_epochs: int = 1               # E: passes over the local microbatch
                                        # schedule (client batch must divide
                                        # by local_steps; cycled E times)
    client_lr: float = 0.002            # eta   (local SGD)
    server_lr: float = 0.002            # eta_g (aggregation step size)
    meta_lr: float = 0.002              # eta_meta
    prox_mu: float = 2e-4               # FedProx coefficient
    server_opt: str = "sgd"             # sgd | sgdm | adam | yogi
    server_momentum: float = 0.0
    cohort_strategy: str = "vmap"       # the chunk size of the streaming
                                        # cohort core when cohort_chunk is
                                        # None: vmap = the whole cohort in
                                        # one slice (client-parallel), scan
                                        # = one client per slice
                                        # (client-sequential); chunked
                                        # needs cohort_chunk
    cohort_chunk: Optional[int] = None  # >=1: stream the cohort through the
                                        # core in slices of this many
                                        # clients — vmap within a slice,
                                        # Pallas FMA accumulation in client
                                        # order, so peak gradient memory is
                                        # one chunk instead of the whole
                                        # cohort.  Results are bit-identical
                                        # for every chunk size (a ragged
                                        # final chunk is padded with
                                        # zero-weight clients).  None keeps
                                        # the configured cohort_strategy;
                                        # incompatible with
                                        # cohort_strategy='scan' (scan IS
                                        # the chunk=1 pin of the same core).
    remat_local_steps: bool = True      # jax.checkpoint each keep-trace step
    lr_decay: float = 1.0               # multiplicative per-round client-lr decay
    grad_agg_dtype: str = "float32"     # dtype of the aggregate's tree
                                        # view (the legacy_tree engine's
                                        # handle); flat handles are fp32
    clip_norm: float = 0.0              # >0: clip the aggregated gradient G
                                        # (tames UGA's HVP amplification — the
                                        # instability the paper notes in §4.5.1)
    fused_update: bool = False          # fused flat-buffer Pallas server step
                                        # (clip->apply in one HBM pass over
                                        # the streamed aggregate;
                                        # kernels/fused_update).  False
                                        # keeps the legacy tree-map path.
                                        # Implies fp32 aggregation (the fused
                                        # kernels ignore grad_agg_dtype).
    meta_mode: str = "post"             # 'post': Eq. (20) server meta step
                                        # after aggregation (the paper's §3.2,
                                        # default).  'through_aggregation':
                                        # backprop the D_meta loss THROUGH the
                                        # fused server step (custom-VJP Pallas
                                        # backward) into hypergradients for the
                                        # per-client aggregation weights and
                                        # the server step size, held in the
                                        # server state's 'ctrl' slot and
                                        # updated each round with ctrl_lr.
                                        # Requires fused_update; every
                                        # synchronous executor supports it
                                        # (core/meta.py
                                        # meta_update_through_cohort).
    ctrl_lr: float = 0.01               # hypergradient step size for the
                                        # controllable-weights state
                                        # (meta_mode='through_aggregation')
    participation: float = 1.0          # <1: partial participation /
                                        # straggler dropout — each round
                                        # keeps a client with this prob and
                                        # zeroes dropped clients' weights
                                        # inside the aggregation (every
                                        # executor/engine supports it)
    engine: Optional[str] = None        # server-engine registry name
                                        # (repro.core.engines); None derives
                                        # legacy_tree / fused_flat from
                                        # fused_update.  A registered custom
                                        # engine declaring the
                                        # through_aggregation capability
                                        # makes that meta_mode valid
                                        # regardless of fused_update.
    codec: str = "none"                 # gradient-codec registry name
                                        # (repro.comm): the client->server
                                        # uplink transport.  'none' ships
                                        # fp32 (bit-identical to a codec-
                                        # free round); 'int8' / 'sign1bit'
                                        # / 'topk' are lossy — they need a
                                        # flat-consuming engine
                                        # (fused_update=True) and are
                                        # meta_mode='post' only.
    error_feedback: bool = False        # keep each client's compression
                                        # residual in state["comm"] and add
                                        # it back before the next round's
                                        # encode (EF-SGD memory; restores
                                        # convergence under aggressive
                                        # codecs).  Requires a lossy codec.
    topk_ratio: float = 0.01            # fraction of largest-|g| elements
                                        # the 'topk' codec ships per dtype
                                        # group
    # ---- buffered-async runtime (engine='buffered_async') ----------------
    async_buffer: int = 0               # K: server steps every K arrived
                                        # deltas (0 -> cohort, i.e. one step
                                        # per fault-free tick)
    async_capacity: int = 0             # delta-pool slots (0 -> 2*cohort);
                                        # overflow evicts the stalest delta
    async_max_staleness: int = 0        # >0: evict arrived deltas older
                                        # than this many server versions
    staleness_mode: str = "invsqrt"     # flush-weight discount of a stale
                                        # delta: 'invsqrt' (FedBuff
                                        # 1/sqrt(1+s)) | 'inv' | 'none'
    # ---- client fault injection (repro.sim.faults) ------------------------
    fault_profile: str = "none"         # named profile ('none' | 'flaky' |
                                        # 'stragglers'); fault_* fields >= 0
                                        # override individual rates
    fault_drop: float = -1.0            # P(uplink report lost)
    fault_crash: float = -1.0           # P(client dies mid-round)
    fault_delay: float = -1.0           # P(report delivered rounds late)
    fault_max_delay: int = -1           # late reports land U{1..max_delay}
                                        # ticks late (async pool buffers
                                        # them; the sync barrier waits)
    fault_garble: float = -1.0          # P(payload corrupted) — the async
                                        # delta pool only; explicit garble
                                        # on a sync engine is a config error
    fault_garble_scale: float = -1.0    # corrupted payload multiplier range
    fault_speed_tail: float = -1.0      # lognormal sigma of client compute
                                        # time (simulated-latency model)
    round_deadline: float = 0.0         # sync barrier only: >0 drops any
                                        # client whose simulated completion
                                        # exceeds this many round-units
                                        # (async replaces the barrier — use
                                        # async_max_staleness there)
    retry_backoff: int = 0              # trainer policy: >0 re-enqueues a
                                        # crashed/dropped/timed-out client
                                        # after backoff * 2^attempt rounds
    retry_max: int = 3                  # retry attempts per client failure

    def __post_init__(self):
        # registry-backed validation (lazy imports: repro.core modules
        # import this one at module load, the registries only at use time)
        from repro.core.algorithms import get_algorithm
        from repro.core.executors import available_executors
        get_algorithm(self.algorithm)          # raises naming the registry
        # "sharded" is a modifier executor (selected by grad_shardings,
        # wrapping THIS field as its base strategy), not a base strategy
        base_strategies = tuple(n for n in available_executors()
                                if n != "sharded")
        if self.cohort_strategy not in base_strategies:
            raise ValueError(
                f"unknown cohort_strategy {self.cohort_strategy!r}; "
                f"registered base cohort executors: {base_strategies} "
                "(the 'sharded' executor is selected by passing "
                "grad_shardings to make_federated_round, not here)")
        if self.cohort_chunk is not None:
            if self.cohort_chunk < 1:
                raise ValueError(
                    f"cohort_chunk={self.cohort_chunk} must be >= 1: it is "
                    "the number of clients the chunked executor vmaps per "
                    "streaming slice (a ragged final chunk is padded with "
                    "zero-weight clients, never truncated)")
            if self.cohort_strategy == "scan":
                raise ValueError(
                    f"cohort_chunk={self.cohort_chunk} together with "
                    "cohort_strategy='scan' is ambiguous: scan IS the "
                    "chunked streaming core pinned at chunk=1 (one client "
                    "alive at a time). Drop cohort_chunk to keep scan, or "
                    "drop cohort_strategy='scan' (keep the default 'vmap' "
                    "or set 'chunked') so cohort_chunk selects the slice "
                    "size.")
        elif self.cohort_strategy == "chunked":
            raise ValueError(
                "cohort_strategy='chunked' needs cohort_chunk set: the "
                "chunked executor streams the cohort in cohort_chunk-client "
                "slices. Set e.g. cohort_chunk=8, or use cohort_strategy="
                "'vmap' / 'scan'.")
        assert self.local_steps >= 1
        assert self.local_epochs >= 1
        if not 0.0 < self.participation <= 1.0:
            raise ValueError(
                f"participation={self.participation} must be in (0, 1]: it "
                "is the per-round probability a sampled client reports")
        if self.meta_mode not in ("post", "through_aggregation"):
            # ValueError, not assert: a typo'd mode under python -O would
            # otherwise silently fall through to meta_mode='post' behavior
            raise ValueError(
                f"unknown meta_mode {self.meta_mode!r}; expected 'post' or "
                "'through_aggregation'")
        if self.meta_mode == "through_aggregation":
            # The mode is a *capability the server engine declares*
            # (repro.core.engines); make_federated_round re-checks against
            # the resolved engine, but fail at config time too so the
            # combination is loud in any interpreter mode.
            from repro.core.engines import resolve_engine
            eng = resolve_engine(self)
            if "through_aggregation" not in eng.meta_capabilities:
                raise ValueError(
                    f"meta_mode='through_aggregation' needs a server "
                    f"engine declaring the capability, but {eng.name!r} "
                    f"declares {sorted(eng.meta_capabilities)}; set "
                    "fused_update=True (the fused_flat engine's custom "
                    "VJP) or use meta_mode='post'")
            if not self.server_lr > 0:
                raise ValueError(
                    "meta_mode='through_aggregation' seeds the controllable "
                    "step size as exp(log_lr)=server_lr; server_lr must "
                    "be > 0")
        # communication-compression knobs (repro.comm) — same lazy-import
        # registry validation as the algorithm/executor fields above
        from repro.comm.codecs import get_codec
        if not 0.0 < self.topk_ratio <= 1.0:
            raise ValueError(
                f"topk_ratio={self.topk_ratio} must be in (0, 1]: it is "
                "the fraction of elements the 'topk' codec transmits")
        codec = get_codec(self.codec)(self)    # raises naming the registry
        if self.error_feedback and not codec.lossy:
            raise ValueError(
                f"error_feedback=True with codec={self.codec!r} has no "
                "compression residual to feed back; pick a lossy codec "
                f"(e.g. 'int8', 'sign1bit', 'topk') or drop error_feedback")
        if codec.lossy:
            if self.meta and self.meta_mode == "through_aggregation":
                raise ValueError(
                    f"codec={self.codec!r} cannot combine with meta_mode="
                    "'through_aggregation': the hypergradient would "
                    "differentiate through a non-differentiable quantizer "
                    "(silently treating decoded gradients as exact). Lossy "
                    "codecs are meta_mode='post' only for now — a "
                    "straight-through codec VJP is a ROADMAP follow-up.")
            from repro.core.engines import resolve_engine
            eng = resolve_engine(self)
            if "lossy" not in getattr(eng, "codec_capabilities",
                                      frozenset()):
                raise ValueError(
                    f"codec={self.codec!r} needs a server engine declaring "
                    f"the 'lossy' codec capability, but {eng.name!r} "
                    f"declares {sorted(eng.codec_capabilities)}: lossy "
                    "codecs decode into flat dtype-group buffers. Set "
                    "fused_update=True (the fused_flat engine) or use "
                    "codec='none'.")
        # fault-injection / async-runtime knobs — resolve_faults performs
        # the rate/shape validation (raises naming the bad field)
        from repro.sim.faults import resolve_faults
        resolve_faults(self)
        if self.staleness_mode not in ("none", "inv", "invsqrt"):
            raise ValueError(
                f"unknown staleness_mode {self.staleness_mode!r}; expected "
                "'none', 'inv' or 'invsqrt' (the FedBuff 1/sqrt(1+s) "
                "default)")
        if (self.async_buffer < 0 or self.async_capacity < 0
                or self.async_max_staleness < 0):
            raise ValueError(
                f"async_buffer={self.async_buffer} / async_capacity="
                f"{self.async_capacity} / async_max_staleness="
                f"{self.async_max_staleness} must be >= 0 (0 means the "
                "default: K=cohort, capacity=2*cohort, no staleness bound)")
        if self.retry_backoff < 0 or self.retry_max < 0:
            raise ValueError(
                f"retry_backoff={self.retry_backoff} / retry_max="
                f"{self.retry_max} must be >= 0")
        if self.engine == "buffered_async":
            k = self.async_buffer or self.cohort
            cap = self.async_capacity or 2 * self.cohort
            if k > cap:
                raise ValueError(
                    f"async_buffer={k} exceeds async_capacity={cap}: the "
                    "pool can never hold K deltas, so the server would "
                    "never step (deadlock). Raise async_capacity or lower "
                    "async_buffer.")
            if self.round_deadline > 0:
                raise ValueError(
                    "round_deadline is a synchronous-barrier timeout; the "
                    "buffered_async runtime has no barrier to time out — "
                    "bound lateness with async_max_staleness instead")
