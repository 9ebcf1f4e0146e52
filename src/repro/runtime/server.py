"""LLM serving loop with continuous batching (paper §III-C-3 analog).

The paper measures generation throughput on Llama with ShareGPT-derived
request lengths (Table XII).  One engine serves it here:
``ChunkedServer`` (also exported as ``Server``), Sarathi-style chunked
prefill: prompts are bucketed into fixed C-token chunks and packed,
together with the single-token decodes of ongoing requests, into ONE
fixed-shape jitted step (`models.transformer.chunk_step`).
Decode-only stretches run a device-resident K-step `lax.scan` span:
greedy argmax, position advance, active-slot masking and stop detection
all happen on device; the host only mirrors the (deterministic)
bookkeeping and transfers tokens when harvesting finished requests.
Because every compiled program has a shape fixed by (slots, chunk,
span), the engine compiles O(1) programs no matter how prompt lengths
are distributed (probe: ``compile_counts()``).

KV memory is **paged** (vLLM-style): instead of reserving a contiguous
``max_len`` region per slot up front, the cache is a shared pool of
fixed-size blocks ([num_blocks, KH, block_size, hd] per layer)
addressed through a per-slot block table — a fixed-shape
[slots, max_blocks] int32 jit operand, so the compiled programs are
unchanged in number.  Masked positions carry exactly-zero softmax
weight, so the virtual view through the table reads like a contiguous
cache (the model layer's own contiguous ``decode_step`` path is the
tests' reference).

On top of the paged pool sits a **radix-tree prefix cache**
(``prefix_cache=True``, runtime/prefix_cache.py): finished requests
insert their full-block token prefix into a tree whose leaves point at
physical pool blocks, and ``_admit`` matches each new prompt against
it — shared blocks map straight into the slot's block table (refcount
+1 each), chunked prefill resumes at the first uncached token, and the
admission reservation covers only the uncovered tail.  A prompt that
extends into a shared but partially-matching block copies it to a
private block first (copy-on-write; one jitted block-to-block pool
copy) so cached entries are never mutated.  Freeing is uniformly
``decref``: blocks return to the free list only when no slot and no
tree node holds them, and when the free list runs dry the allocator
evicts refcount-0 cached blocks in LRU order.  Sharing is a pure
host-side table construction — the jitted programs and their O(1)
compile counts are untouched, and greedy outputs stay bit-identical to
``prefix_cache=False`` (cached KV was produced by the same jitted
steps on the same token/position inputs).

With ``eos_id`` set, generation also stops when the model emits that
token: the device-side stop mask of the decode span folds in
``tok == eos_id`` alongside the length checks, at the cost of syncing
the span's final position/stop state back to the host.
``eos_id=None`` (default) preserves the length-only behavior, where
the host mirror never reads device state.

``spec_decode=K`` (default 0 = off) turns the decode-only stretches
speculative (runtime/spec_decode.py): a device-resident n-gram suffix
table drafts up to K tokens per slot, one fixed-shape ``verify_step``
dispatch — the same program shape as a prefill chunk — scores all
B×(K+1) tokens, and the longest draft prefix matching the greedy
argmax chain is accepted plus one bonus token.  Acceptance is exact
for greedy decoding, so outputs are those of ``K=0`` (up to the
verify and decode programs' fp32 summation order); the
rejected suffix's cache writes are rolled back host-side by
truncating the slot's block-table frontier.  One extra compiled
program total: {chunk_step, decode_span, verify_step}.

``sampling=SamplingParams(...)`` (default greedy) sets the server-wide
stochastic decoding head and each ``Request.sampling`` can override it:
temperature / top-k / top-p over an fp32 softmax, drawn on device with
a key folded from ``(per-request seed, emission position)`` — no host
RNG ever enters a span (models/sampling.py).  Greedy is encoded in the
operand VALUES (temperature 0), so greedy and sampled requests share
the same three compiled programs, ``temperature=0``/``top_k=1`` is
bit-identical to the historical argmax engine, and ``spec_decode=K``
composes: the verify chain is sampled with the same position keys, so
speculative sampling is exact-match-given-seed to ``K=0`` sampling
(the point-mass speculative-sampling rule — see
runtime/spec_decode.py).

``kernel=True`` (default off) reads the KV pool through the fused
Pallas block-table kernels of kernels/paged_attention.py instead of
materializing each slot's gathered view: the block-table walk happens
inside the kernel, only the ``ceil(kv_len/block_size)`` valid blocks
move, and the contractions run on the MXU; results agree with
``kernel=False`` within the tolerances of tests/test_paged_kernel.py —
the gather path stays as the always-on A/B oracle.
``fp8_kv=True`` stores the pool as e4m3 codes plus per-token-row f32
scales (halving per-device KV bytes + scale overhead; see
core/roofline.paged_decode_kv_bytes for the modeled bytes/step), and
``fp8_linear=True`` (tp=1, non-MoE) pre-quantizes
the layer weights once at init and serves every matmul through
te/linear.fp8_serving_dot.  The fp8 options change numerics within
documented tolerance (tests/test_paged_kernel.py).

``tp=N`` (default 1) serves **tensor-parallel** over an N-device mesh
(launch/mesh.make_tp_mesh; sharding/plans.ServingPlan documents the
mesh/axis contract): weights shard head-wise / column-row-wise, the KV
pool shards along its KV-head dim, and the three jitted work units run
as single fixed-shape programs over NamedSharding operands — compile
counts stay {chunk_step, decode_span, verify_step}.  Block tables, the
refcounted allocator and the radix tree stay host-side and replicated,
so paging, prefix caching and spec decode compose with TP unchanged.
The only cross-shard float reductions (attention out-projection, MLP
down-projection) run through order-deterministic fixed-tree grouped
sums (models.transformer.serving_det_groups), so on the CPU greedy
outputs at any supported ``tp`` are token-identical to ``tp=1``.  On a
TPU v5e the sharded programs do not round bitwise like the whole one:
at Yi-6B width 5 of 16 greedy requests came out identical at tp=4,
with prompt logits 0.019 relative RMS apart (chip_smoke.py --tp 4
holds them to its bf16 tolerance instead).

The chunked path's per-slot math (bf16 activations, MXU attention dots
accumulating in fp32, fp32 softmax over the masked cache) is the
token-at-a-time decode path's, and the two differ only in the order of
the attention's fp32 sums, so greedy tokens match a plain one-request
``api.decode_step`` loop wherever their logits agree (the CPU tests
hold them equal).  Requests whose ``in_len + max_new`` cannot fit
below the ``max_len`` position cap are flagged ``truncated`` at
admission instead of silently coming back short.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ModelConfig
from repro.launch.mesh import make_tp_mesh
from repro.models import api, sampling, transformer
from repro.models.sampling import GREEDY, SamplingParams
from repro.obs.metrics import MetricsRegistry
from repro.obs.tracer import NULL_TRACER, Tracer
from repro.runtime import spec_decode as spec
from repro.runtime.prefix_cache import BlockPool, RadixPrefixCache
from repro.sharding import axes as axes_mod
from repro.sharding import plans as plans_mod
from repro.te import linear as te_linear

Params = Any

# dispatch kind (span names, RequestRecord.seen_after) -> the phase the
# registry's serving.{dispatches,wall_s,host_s,wait_s}.* are named by
_PHASE = {"chunk": "prefill", "span": "span", "verify": "verify"}


def _in_phase(name: str):
    """Run a scheduler method inside the tracer's ``name`` span."""
    def wrap(fn):
        @functools.wraps(fn)
        def run(self, *a, **k):
            with self.obs.phase(name):
                return fn(self, *a, **k)
        return run
    return wrap


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray              # [in_len] int32
    max_new: int
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # set at admission when in_len + max_new overruns the max_len
    # position cap: generation will stop at max_len - in_len tokens
    # instead of max_new (previously a silent short harvest)
    truncated: bool = False
    # per-request sampling config (models/sampling.SamplingParams);
    # None falls back to the server's default (greedy unless the
    # server was built with sampling=...)
    sampling: Optional[SamplingParams] = None


def sharegpt_like_requests(n: int, vocab: int, *, max_input: int = 128,
                           max_output: int = 128, seed: int = 0
                           ) -> List[Request]:
    """Log-normal length mix approximating the ShareGPT distribution."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        in_len = int(np.clip(rng.lognormal(3.2, 0.8), 4, max_input))
        out_len = int(np.clip(rng.lognormal(3.5, 0.7), 4, max_output))
        prompt = rng.integers(0, vocab, size=in_len).astype(np.int32)
        reqs.append(Request(rid=i, prompt=prompt, max_new=out_len))
    return reqs


def sysprompt_sharegpt_requests(n: int, vocab: int, *,
                                num_templates: int = 2,
                                template_len: int = 64,
                                max_input: int = 128,
                                max_output: int = 128, seed: int = 0
                                ) -> List[Request]:
    """Shared-prefix serving mix: N fixed system-prompt templates, each
    request one template plus a log-normal unique tail — the production
    pattern (millions of users hitting the same few system prompts /
    few-shot templates) that the radix prefix cache turns from repeated
    prefill compute into block-table lookups."""
    assert template_len < max_input
    rng = np.random.default_rng(seed)
    templates = [rng.integers(0, vocab, size=template_len).astype(np.int32)
                 for _ in range(num_templates)]
    reqs = []
    for i in range(n):
        t = templates[int(rng.integers(num_templates))]
        tail_len = int(np.clip(rng.lognormal(2.0, 0.8), 1,
                               max_input - template_len))
        out_len = int(np.clip(rng.lognormal(3.5, 0.7), 4, max_output))
        tail = rng.integers(0, vocab, size=tail_len).astype(np.int32)
        reqs.append(Request(rid=i, prompt=np.concatenate([t, tail]),
                            max_new=out_len))
    return reqs


def repetitive_requests(n: int, vocab: int, *, num_motifs: int = 2,
                        motif_len: int = 8, reps: int = 3,
                        max_output: int = 48, seed: int = 0
                        ) -> List[Request]:
    """Highly repetitive mix: each prompt tiles one of a few short
    motifs, so identical requests recur within and across waves — the
    retried/templated-generation traffic that is the n-gram draft
    proposer's best case (greedy outputs of a repeated prompt repeat
    too, and the shared suffix table replays them).  Spec-decode A/Bs
    on this mix show accepted-tokens-per-step well above 1 even on CPU
    CI, where a model-based drafter would drown in dispatch overhead."""
    rng = np.random.default_rng(seed)
    motifs = [rng.integers(0, vocab, size=motif_len).astype(np.int32)
              for _ in range(num_motifs)]
    reqs = []
    for i in range(n):
        motif = motifs[int(rng.integers(num_motifs))]
        reqs.append(Request(rid=i, prompt=np.tile(motif, reps),
                            max_new=max_output))
    return reqs


def clone_requests(reqs: List[Request]) -> List[Request]:
    """Fresh Request objects for re-serving the same mix (A/B runs)."""
    return [Request(rid=r.rid, prompt=r.prompt.copy(), max_new=r.max_new,
                    sampling=r.sampling)
            for r in reqs]


# ----------------------------------------------------------------------
# Chunked-prefill engine
# ----------------------------------------------------------------------

class ChunkedServer:
    """Chunked-prefill continuous-batching server (transformer family).

    Fixed-shape work units:
      * chunk step  — [slots, chunk] tokens; prefilling slots consume up
        to `chunk` prompt tokens, decoding slots piggyback their next
        token at row 0 (Sarathi-style coalescing).
      * decode span — `span` consecutive decode steps scanned on device
        when no prefill is pending.
      * verify step — with ``spec_decode=K``, decode-only stretches
        instead run one [slots, K+1] speculative window per dispatch:
        n-gram drafts verified against the model's own argmax chain
        (the span loop's emissions, >= 1 token per slot per dispatch).

    The host mirrors position/emission bookkeeping in numpy — greedy
    decoding with length-only stopping is fully deterministic, so the
    mirror never needs to read device state; tokens cross to the host
    only when a finished request is harvested.  With ``eos_id`` set the
    stop rule additionally depends on emitted tokens, so the span's
    final pos/out_len/active state syncs back instead.  All mirror
    arrays are int32 (matching the jit operands) so operand dtypes
    never drift between calls.

    The KV cache is a shared block pool plus per-slot block tables; `_ensure_blocks` assigns physical
    blocks as a slot's frontier advances and `_harvest` drops the
    slot's references.  ``_admit`` reserves the request's worst case
    *minus its prefix-cache hit* against the pool and backpressures
    (leaves the queue head waiting) when it cannot, instead of capping
    concurrency at a fixed per-slot max_len region.  With
    ``prefix_cache=True`` finished requests feed a radix tree of
    full-block token runs; admission maps matched blocks into the
    table, resumes prefill at the first uncached token, and
    copy-on-writes when the request extends into a shared,
    partially-matching block.

    With a ``Tracer`` the scheduler writes host spans into a profiler
    trace (``Tracer.phase``): ``serve.step`` (one loop iteration,
    ``step_num`` = the loop counter) holding ``serve.poll``,
    ``serve.admit``, ``serve.sleep``, ``serve.harvest`` and, per
    dispatch kind (chunk, span, verify), ``serve.<kind>.prepare`` /
    ``.put`` / ``.enqueue`` / ``.wait`` / ``.commit``.  Always, the
    registry splits each dispatch's ``serving.wall_s.<phase>`` into
    ``serving.host_s.<phase>`` (entry to the jitted call's return) and
    ``serving.wait_s.<phase>`` (that return to ``block_until_ready``),
    and ``serving.gap_s`` holds the host time between dispatches.
    ``serving.sampling.path.{greedy,nucleus,top_k}`` count each chunk
    step and each step of a span by the branch its sample head takes
    (``sampling.batch_path`` over the mirrors; a span's live rows are
    its mirror simulation's, which EOS can only narrow on device).
    """

    # the KV cache is always the paged pool; bench/harness.warm reads this
    paged = True

    def __init__(self, cfg: ModelConfig, params: Params, *,
                 batch_slots: int = 8, max_len: int = 512,
                 chunk: int = 16, span: int = 8,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 prefix_cache: bool = True,
                 eos_id: Optional[int] = None,
                 spec_decode: int = 0,
                 spec_n_ctx: int = spec.DEFAULT_N_CTX,
                 kernel: bool = False, fp8_kv: bool = False,
                 fp8_linear: bool = False,
                 tp: int = 1, mesh=None,
                 sampling: Optional[SamplingParams] = None,
                 tracer: Optional[Tracer] = None):
        assert cfg.family in ("dense", "moe", "vlm")
        self.cfg = cfg
        self.B = batch_slots
        self.max_len = max_len
        self.chunk = chunk
        self.span = span
        self.eos_id = eos_id
        # -- stochastic sampling (models/sampling): the server default
        # for requests without their own SamplingParams.  Greedy is a
        # VALUE (temperature=0), not a program variant: the sample
        # operands are always present in every work unit's signature,
        # so greedy<->sampled flips never recompile (JX005) and the
        # per-slot mirrors below are just four more int32/f32 scheduler
        # vectors crossing through _put.
        self.sampling = sampling if sampling is not None else GREEDY
        # -- observability (repro.obs): `self.obs` records lifecycle
        # events only when a Tracer is passed (NULL_TRACER's methods
        # are no-ops and `enabled=False` skips arg construction at the
        # call sites); `self.metrics` is ALWAYS a real registry so the
        # per-phase dispatch/wall-time breakdown exists even untraced.
        # Both are host-side only: timestamps wrap jitted dispatches
        # (after block_until_ready), never enter them.
        self.obs = tracer if tracer is not None else NULL_TRACER
        self.metrics = (tracer.metrics if tracer is not None
                        else MetricsRegistry())
        # -- serving hot-path variants (models/transformer fwd kwargs):
        # kernel=True reads paged KV through the fused Pallas
        # block-table kernels (kernels/paged_attention; within stated
        # tolerance of the gather path, so kernel=False stays the
        # always-available A/B oracle); fp8_kv stores the pool
        # as e4m3 + per-row scales; fp8_linear pre-quantizes the layer
        # weights once and serves matmuls through te/linear.
        self.kernel = bool(kernel)
        self.fp8_kv = bool(fp8_kv)
        self.fp8_linear = bool(fp8_linear)
        # -- tensor-parallel mesh (sharding/plans.ServingPlan contract):
        # weights head-wise/column-row-wise, KV cache along the KV-head
        # axis, every scheduler operand (tokens, positions, block
        # tables, out_buf, n-gram table) replicated — the host-side
        # allocator/radix tree never learn the mesh exists, so paging,
        # prefix sharing and spec decode compose with TP unchanged.
        self.mesh = mesh
        if self.mesh is None and tp > 1:
            self.mesh = make_tp_mesh(tp)
        self._plan = None
        self.tp = 1
        if self.mesh is not None:
            assert len(self.mesh.axis_names) == 1, \
                "serving mesh must have exactly one (tensor-parallel) axis"
            self._plan = plans_mod.serving_plan(
                self.mesh, axis=self.mesh.axis_names[0])
            self.tp = self._plan.tp
        if self.tp > 1:
            assert cfg.family != "moe", \
                "tensor-parallel serving is dense/vlm-only for now"
            assert cfg.num_kv_heads % self.tp == 0, \
                (f"tp={self.tp} must divide num_kv_heads="
                 f"{cfg.num_kv_heads} (the KV pool shards head-wise)")
            ga, gm = transformer.serving_det_groups(cfg)
            assert ga % self.tp == 0 and gm % self.tp == 0, \
                (f"tp={self.tp} must divide the deterministic reduction "
                 f"groups (attn={ga}, mlp={gm}) for exact tp-vs-1 "
                 f"output parity")
        if self._plan is not None:
            self._param_sh = self._plan.param_shardings(cfg)
            self._cache_sh = self._plan.cache_sharding(cfg)
            self._repl = self._plan.replicated
            params = jax.device_put(params, self._param_sh)
        self.params = params
        self._quant = None
        if self.fp8_linear:
            assert self.tp == 1, \
                ("fp8_linear serving is tp=1-only: the fp8 path has no "
                 "grouped order-deterministic reduction structure")
            assert cfg.family != "moe", \
                "fp8_linear serving is dense/vlm-only for now"
            self._quant = te_linear.quantize_serving_params(self.params)
        self.spec_decode = int(spec_decode)
        assert self.spec_decode >= 0
        self.prefix_cache: Optional[RadixPrefixCache] = None
        self.block_size = block_size
        # virtual blocks per slot; real writes never pass max_len
        self.max_blocks = -(-max_len // block_size)
        self.num_blocks = (batch_slots * self.max_blocks
                           if num_blocks is None else num_blocks)
        self.cache = api.init_cache(
            cfg, batch_slots, max_len, paged=True,
            block_size=block_size, num_blocks=self.num_blocks,
            sharding=(self._cache_sh if self._plan is not None
                      else None),
            fp8_kv=self.fp8_kv)
        self.block_table = np.full((batch_slots, self.max_blocks),
                                   -1, np.int32)
        self.pool = BlockPool(self.num_blocks)
        if prefix_cache:
            self.prefix_cache = RadixPrefixCache(self.pool, block_size,
                                                 tracer=self.obs,
                                                 metrics=self.metrics)
        self._slot_blocks: List[List[int]] = [[] for _ in range(batch_slots)]
        self._num_shared = np.zeros(batch_slots, np.int32)
        self._cow_pending = [False] * batch_slots
        self._reserved = np.zeros(batch_slots, np.int32)
        self._reserved_total = 0
        self.peak_blocks = 0
        self.admission_stalls = 0
        self.total_prompt_tokens = 0
        self.cached_prompt_tokens = 0
        self.prefix_hits = 0
        # donating the cache keeps the COW copy in place — without
        # it, XLA materializes a second full pool to update 1 block
        self._cow_fn = jax.jit(
            lambda cache, src, dst: api.cow_copy_block(cfg, cache,
                                                       src, dst),
            donate_argnums=(0,),
            **self._sharding_kw(n_ops=2, with_params=False))
        self.cur_tok = jnp.zeros((batch_slots,), jnp.int32)
        self.out_buf = jnp.zeros((batch_slots, max_len), jnp.int32)
        if self._plan is not None:
            # device-resident replicated state (tokens only cross to
            # the host at harvest, same as the single-device engine)
            self.cur_tok = jax.device_put(self.cur_tok, self._repl)
            self.out_buf = jax.device_put(self.out_buf, self._repl)
        # host-owned mirror (deterministic; never read back from device
        # unless eos stopping is on)
        self.pos = np.zeros(batch_slots, np.int32)
        self.out_len = np.zeros(batch_slots, np.int32)
        self.slot_req: List[Optional[Request]] = [None] * batch_slots
        self.mode = ["idle"] * batch_slots    # idle | prefill | decode | done
        self.prompt_off = np.zeros(batch_slots, np.int32)
        # per-slot sampling mirrors (filled at admission; idle slots
        # hold greedy values, so they can never draw)
        self.samp_temp = np.zeros(batch_slots, np.float32)
        self.samp_top_k = np.zeros(batch_slots, np.int32)
        self.samp_top_p = np.ones(batch_slots, np.float32)
        self.samp_seed = np.zeros(batch_slots, np.int32)
        # donate_argnums=(1,): the KV cache (operand 1, after params)
        # is consumed and rebound from the outputs on every dispatch,
        # so donating it lets XLA update the pool in place — without
        # it each step materializes a second full cache (the same
        # reasoning as the COW copy's donate above; repro.analysis
        # rule JX003 gates this statically)
        self._chunk_fn = jax.jit(self._chunk_impl, donate_argnums=(1,),
                                 **self._sharding_kw(n_ops=13, n_out=2))
        self._span_fn = jax.jit(self._span_impl, donate_argnums=(1,),
                                **self._sharding_kw(n_ops=11, n_out=5))
        if self.spec_decode:
            self.ngram_table = spec.init_ngram_table(
                self.spec_decode, spec_n_ctx)
            if self._plan is not None:
                self.ngram_table = jax.device_put(self.ngram_table,
                                                  self._repl)
            self._verify_fn = jax.jit(self._spec_impl,
                                      donate_argnums=(1,),
                                      **self._sharding_kw(n_ops=12,
                                                          n_out=7))
            self.spec_steps = 0
            self.spec_slot_steps = 0
            self.spec_drafted = 0
            self.spec_accepted = 0
            self.spec_emitted = 0
        # scheduler loop counter (the serve.step span's step_num and the
        # dispatch events' `step`), the kind of the last dispatch, and
        # its ready stamp (the start of the host gap before the next)
        self._step = 0
        self._last_kind: Optional[str] = None
        self._t_ready: Optional[float] = None
        if self.obs.enabled:
            # server geometry next to the events (obs/windows.py)
            self.obs.meta.update(
                batch_slots=self.B, chunk=self.chunk, span=self.span,
                max_len=self.max_len, spec_decode=self.spec_decode,
                tp=self.tp, num_blocks=self.num_blocks)

    def _sharding_kw(self, *, n_ops: int, n_out: Optional[int] = None,
                     with_params: bool = True) -> Dict[str, Any]:
        """jit kwargs for a serving work unit under the TP mesh:
        in_shardings = (params tree, cache, then `n_ops` replicated
        operands); out_shardings = (cache, then `n_out` replicated
        results) — pinning the outputs keeps the carried state's
        sharding identical across calls, so each work unit compiles
        exactly once (an unpinned GSPMD output choice would retrace the
        second call).  ``n_out=None`` marks a bare-cache result (the
        COW copy).  Empty (plain single-device jit) with no mesh."""
        if self._plan is None:
            return {}
        lead = (self._param_sh,) if with_params else ()
        out = (self._cache_sh if n_out is None
               else (self._cache_sh,) + (self._repl,) * n_out)
        return {"in_shardings": lead + (self._cache_sh,)
                + (self._repl,) * n_ops,
                "out_shardings": out}

    def _trace_ctx(self):
        """Activation-sharding rules (ServingPlan.act_rules) applied at
        jit trace time so `constrain` calls inside the model bodies
        keep heads/kv_heads/mlp/vocab activations on the tp axis."""
        if self._plan is None:
            return contextlib.nullcontext()
        return axes_mod.use_rules(self.mesh, self._plan.act_rules)

    def _fwd_kw(self) -> Dict[str, Any]:
        """Transformer forward kwargs for this server's hot-path
        variant (kernel/quant/mesh), closed over by the jitted work
        units — the pre-quantized fp8 weights are jit constants, which
        is exactly right for frozen serving weights."""
        kw: Dict[str, Any] = {}
        if self.kernel:
            kw["kernel"] = True
            if self.mesh is not None:
                kw["mesh"] = self.mesh
                kw["mesh_axis"] = self.mesh.axis_names[0]
        if self._quant is not None:
            kw["quant"] = self._quant
        return kw

    def _device_block_table(self) -> np.ndarray:
        """Snapshot of the block table as a jit operand (fixed shape)."""
        return self.block_table.copy()

    def _put(self, x):
        """EXPLICIT host->device transfer for a scheduler operand.

        Every np operand crosses through here so the serve loop runs
        clean under ``jax.transfer_guard("disallow")`` — the dynamic
        pin of the transfer-free contract the analyzer checks
        statically (AST001): the only host->device traffic is the
        scheduler's intent (a few hundred int32s), never activations
        or cache.  Under a TP mesh the operand lands replicated, the
        same placement the work units' in_shardings pin."""
        if self._plan is not None:
            return jax.device_put(x, self._repl)
        return jax.device_put(x)

    # -- jitted work units ------------------------------------------------
    def _chunk_impl(self, params, cache, cur_tok, out_buf, tokens_host,
                    pos, n_tokens, is_decode, emit, out_len,
                    samp_temp, samp_top_k, samp_top_p, samp_seed,
                    block_table):
        with self._trace_ctx():
            B, C = tokens_host.shape
            col0 = jnp.arange(C, dtype=jnp.int32) == 0
            tokens = jnp.where(is_decode[:, None] & col0[None, :],
                               cur_tok[:, None], tokens_host)
            logits, cache = transformer.chunk_step(
                self.cfg, params, cache, tokens, pos, n_tokens,
                block_table, **self._fwd_kw())
            # the emitted token will sit at sequence position
            # pos + n_tokens — the position key that makes this draw
            # identical to the span/verify paths' draw for the same
            # position (models/sampling, greedy when temp<=0); rows
            # that emit nothing pass as greedy, so that only the
            # emitting rows choose the head's work
            nxt = sampling.sample_tokens(
                logits, jnp.where(emit, samp_temp, 0.0), samp_top_k,
                samp_top_p, samp_seed, pos + n_tokens)
            cur_tok = jnp.where(emit, nxt, cur_tok)
            row = jnp.arange(B)
            idx = jnp.clip(out_len, 0, out_buf.shape[1] - 1)
            out_buf = out_buf.at[row, idx].set(
                jnp.where(emit, nxt, out_buf[row, idx]))
            return cache, cur_tok, out_buf

    def _span_impl(self, params, cache, cur_tok, out_buf, pos, out_len,
                   active, max_new, samp_temp, samp_top_k, samp_top_p,
                   samp_seed, block_table):
        with self._trace_ctx():
            return self._span_body(params, cache, cur_tok, out_buf, pos,
                                   out_len, active, max_new, samp_temp,
                                   samp_top_k, samp_top_p, samp_seed,
                                   block_table)

    def _span_body(self, params, cache, cur_tok, out_buf, pos, out_len,
                   active, max_new, samp_temp, samp_top_k, samp_top_p,
                   samp_seed, block_table):
        row = jnp.arange(self.B)
        cap = self.max_len - 1

        def step(carry, _):
            cache, tok, pos, out_buf, out_len, active = carry
            logits, cache = transformer.decode_step(
                self.cfg, params, cache, tok, pos, block_table,
                **self._fwd_kw())
            # emission position pos + 1 (pre-increment), matching the
            # chunk path's pos + n_tokens and verify row j's
            # pos + 1 + j — same (seed, position) -> same draw; idle
            # rows pass as greedy, as in the chunk step
            nxt = sampling.sample_tokens(
                logits, jnp.where(active, samp_temp, 0.0), samp_top_k,
                samp_top_p, samp_seed, pos + 1)
            idx = jnp.clip(out_len, 0, out_buf.shape[1] - 1)
            out_buf = out_buf.at[row, idx].set(
                jnp.where(active, nxt, out_buf[row, idx]))
            inc = active.astype(jnp.int32)
            out_len = out_len + inc
            pos = pos + inc
            tok = jnp.where(active, nxt, tok)
            active = active & (out_len < max_new) & (pos < cap)
            if self.eos_id is not None:
                # device-side EOS stop, folded into the existing mask:
                # the EOS token itself is emitted, then the slot stops
                active = active & (nxt != self.eos_id)
            return (cache, tok, pos, out_buf, out_len, active), None

        carry = (cache, cur_tok, pos, out_buf, out_len, active)
        carry, _ = lax.scan(step, carry, None, length=self.span)
        cache, cur_tok, pos, out_buf, out_len, active = carry
        return cache, cur_tok, out_buf, pos, out_len, active

    def _spec_impl(self, params, cache, table, cur_tok, out_buf, pos,
                   out_len, active, max_new, samp_temp, samp_top_k,
                   samp_top_p, samp_seed, block_table):
        with self._trace_ctx():
            return spec.spec_decode_step(
                self.cfg, params, cache, table, cur_tok, out_buf, pos,
                out_len, active, max_new, samp_temp, samp_top_k,
                samp_top_p, samp_seed, block_table,
                max_len=self.max_len, eos_id=self.eos_id,
                fwd_kw=self._fwd_kw())

    def compile_counts(self) -> Dict[str, int]:
        """Programs compiled per work unit — O(1) by construction."""
        counts = {"chunk_step": api.compile_count(self._chunk_fn),
                  "decode_span": api.compile_count(self._span_fn),
                  "cow_copy": max(api.compile_count(self._cow_fn), 0)}
        if self.spec_decode:
            counts["verify_step"] = api.compile_count(self._verify_fn)
        return counts

    def prompt_logits(self, prompts: List[np.ndarray]) -> np.ndarray:
        """Last-position logits [n, vocab] (f32, on the host) of each
        prompt through this server's serving forward — its chunk size,
        block size, KV read path (gather/kernel), pool dtype and mesh —
        on a scratch paged pool of its own, so the served pool and the
        radix tree are untouched.  These are the logits a request's
        first token is drawn from; checks compare them with a
        reference (api.prefill).  The serving loop never calls this."""
        n, C, mb = len(prompts), self.chunk, self.max_blocks
        cache = api.init_cache(
            self.cfg, n, self.max_len, paged=True,
            block_size=self.block_size, num_blocks=n * mb,
            sharding=self._cache_sh if self._plan is not None else None,
            fp8_kv=self.fp8_kv)
        table = self._put(np.arange(n * mb, dtype=np.int32).reshape(n, mb))

        def step(params, cache, tokens, pos, n_tokens):
            with self._trace_ctx():
                return transformer.chunk_step(
                    self.cfg, params, cache, tokens, pos, n_tokens, table,
                    **self._fwd_kw())

        fn = jax.jit(step, donate_argnums=(1,))
        lens = np.array([len(p) for p in prompts], np.int32)
        out = np.zeros((n, self.cfg.vocab_size), np.float32)
        for off in range(0, int(lens.max()), C):
            tokens = np.zeros((n, C), np.int32)
            n_tok = np.clip(lens - off, 0, C).astype(np.int32)
            for i, p in enumerate(prompts):
                tokens[i, :n_tok[i]] = p[off:off + n_tok[i]]
            logits, cache = fn(self.params, cache, self._put(tokens),
                               self._put(np.full(n, off, np.int32)),
                               self._put(n_tok))
            last = (lens > off) & (lens <= off + C)
            if last.any():
                out[last] = np.asarray(jax.device_get(logits),
                                       np.float32)[last]
        return out

    # -- host-side refcounted block allocator -----------------------------
    def _blocks_needed(self, req: Request) -> int:
        """Worst-case block demand: the frontier never passes
        min(in_len + max_new, max_len)."""
        span_len = min(len(req.prompt) + req.max_new, self.max_len)
        return -(-span_len // self.block_size)

    def _available_blocks(self) -> int:
        """Blocks admission may still promise: free + evictable cached,
        minus reservations already outstanding."""
        ev = (self.prefix_cache.evictable_blocks()
              if self.prefix_cache is not None else 0)
        return self.pool.num_free() + ev - self._reserved_total

    def _blocks_in_use(self) -> int:
        """Working set: blocks currently pinned or owned by a request.
        Refcount-0 tree residue is reclaimable on demand and excluded,
        so peak/pool-utilization keep measuring concurrent demand (the
        PR-2 footprint metric), not cache residency — residency is
        reported separately as ``cached_blocks``."""
        in_use = self.num_blocks - self.pool.num_free()
        if self.prefix_cache is not None:
            in_use -= self.prefix_cache.evictable_blocks()
        return in_use

    def _reclaim(self, n: int) -> None:
        """Grow the free list to ≥ n blocks with ONE LRU eviction sweep
        (an evict() call walks the radix tree, so callers batch their
        whole deficit instead of evicting block by block).  Admission
        accounting guarantees the evictable supply covers every
        reservation."""
        deficit = n - self.pool.num_free()
        if deficit > 0:
            assert self.prefix_cache is not None, "block pool over-committed"
            freed = self.prefix_cache.evict(deficit)
            assert freed >= deficit, \
                "block pool over-committed (nothing evictable)"

    def _take_block(self) -> int:
        """One owned block (refcount 1), evicting when the list is dry."""
        self._reclaim(1)
        return self.pool.alloc()

    def _match_prefix(self, prompt: np.ndarray
                      ) -> Tuple[List[int], Optional[int], int]:
        """Radix lookup, capped so at least the last prompt token is
        recomputed (its logits seed generation).  Returns (shared full
        blocks, copy-on-write block, matched tokens inside it)."""
        full, partial, plen = self.prefix_cache.match(prompt)
        bs = self.block_size
        # max(..., 0) keeps zero-length prompts (served as an immediate
        # emit, as before this cache existed) out of the index math
        usable = max(min(len(full) * bs + plen, len(prompt) - 1), 0)
        nfull = usable // bs
        cow_len = usable - nfull * bs
        if cow_len < max(bs // 2, 1):
            # a short partial overlap (e.g. a universal BOS token at
            # the root) isn't worth a block copy, and counting it as a
            # hit would read ~1.0 hit-rate on traffic with no real
            # sharing; recompute those few tokens instead
            return full[:nfull], None, 0
        # the capped frontier landed inside a matched block: map it
        # shared and let _ensure_blocks copy it before the first write
        cow = full[nfull] if nfull < len(full) else partial
        return full[:nfull], cow, cow_len

    def _ensure_blocks(self, s: int, upto: int) -> None:
        """Assign physical blocks so slot s covers virtual [0, upto),
        resolving a pending copy-on-write before the write frontier
        reaches the shared block."""
        bs = self.block_size
        owned = self._slot_blocks[s]
        need = -(-upto // bs)
        # one batched eviction sweep for everything this call will
        # allocate: the COW copy target plus the frontier growth
        cow_now = (self._cow_pending[s]
                   and upto > int(self._num_shared[s]) * bs)
        self._reclaim(max(need - len(owned), 0) + bool(cow_now))
        if cow_now:
            ci = int(self._num_shared[s])
            src = owned[ci]
            dst = self._take_block()
            self.cache = self._cow_fn(self.cache,
                                      self._put(np.int32(src)),
                                      self._put(np.int32(dst)))
            self.block_table[s, ci] = dst
            owned[ci] = dst
            self.pool.decref(src)
            self._reserved[s] -= 1
            self._reserved_total -= 1
            self._cow_pending[s] = False
            self.metrics.counter("serving.cow.resolves").inc()
            if self.obs.enabled:
                self.obs.event("cow_resolve", slot=int(s), src=int(src),
                               dst=int(dst))
        assert need - len(owned) <= self._reserved[s], \
            f"slot {s}: demand {need} blocks exceeds reservation"
        while len(owned) < need:
            b = self._take_block()
            self.block_table[s, len(owned)] = b
            owned.append(b)
            self._reserved[s] -= 1
            self._reserved_total -= 1
        in_use = self._blocks_in_use()
        self.peak_blocks = max(self.peak_blocks, in_use)
        self.metrics.gauge("serving.pool.blocks_in_use").set(float(in_use))

    def _truncate_blocks(self, s: int, upto: int) -> int:
        """Roll slot s's block-table frontier back so it owns exactly
        the blocks covering virtual [0, upto) — the paged-cache
        rollback after a verify step rejects draft tokens.  Blocks
        wholly beyond the frontier return to the pool and their
        admission reservation is restored (they were drawn from it by
        `_ensure_blocks` pre-verify).  Only frontier growth is ever
        rolled back: shared prefix blocks and a resolved COW copy all
        sit below the decode frontier, so refcount/COW invariants are
        untouched.  Stale KV the rejected rows scattered beyond `upto`
        lands where the position masks never read and the next write
        window lands first (see attention.update_paged_cache).
        Returns the number of blocks rolled back."""
        owned = self._slot_blocks[s]
        keep = -(-upto // self.block_size)
        assert keep >= int(self._num_shared[s]) + bool(self._cow_pending[s])
        freed = 0
        while len(owned) > keep:
            b = owned.pop()
            self.block_table[s, len(owned)] = -1
            self.pool.decref(b)
            self._reserved[s] += 1
            self._reserved_total += 1
            freed += 1
        return freed

    def _free_slot_blocks(self, s: int) -> None:
        """free == decref: cached blocks stay resident (evictable),
        exclusively-owned blocks return to the free list."""
        for b in self._slot_blocks[s]:
            self.pool.decref(b)
        self._slot_blocks[s] = []
        self._num_shared[s] = 0
        self._cow_pending[s] = False
        self.block_table[s, :] = -1
        self._reserved_total -= int(self._reserved[s])
        self._reserved[s] = 0

    # -- host-side scheduling --------------------------------------------
    @_in_phase("serve.admit")
    def _admit(self, queue: List[Request]) -> None:
        for s in range(self.B):
            if self.slot_req[s] is None and queue:
                req = queue[0]
                if len(req.prompt) > self.max_len:
                    # its positions would run past the slot's block table
                    # (max_len tokens) and be served silently cut
                    queue.pop(0)
                    raise ValueError(
                        f"request {req.rid}: prompt length "
                        f"{len(req.prompt)} exceeds max_len {self.max_len}")
                matched = 0
                shared: List[int] = []
                cow, cow_len = None, 0
                # cheap lower bound first: when even a fully-cached
                # prompt could not admit, skip the radix walk and
                # pin/rollback churn that a stalled queue head
                # would otherwise replay every serve-loop iteration
                best_shared = (max((len(req.prompt) - 1)
                                   // self.block_size, 0)
                               if self.prefix_cache is not None else 0)
                fail_fast = (self._blocks_needed(req) - best_shared
                             > self._available_blocks())
                if not fail_fast and self.prefix_cache is not None:
                    shared, cow, cow_len = self._match_prefix(req.prompt)
                    # pin the hit before the supply check; matched
                    # blocks are mapped, not drawn from the pool
                    for b in shared:
                        self.pool.incref(b)
                    if cow is not None:
                        self.pool.incref(cow)
                    matched = len(shared) * self.block_size + cow_len
                # worst case minus the cache-covered prefix: a
                # fully-cached prompt admits even when the free
                # pool alone couldn't hold its unshared footprint
                needed = self._blocks_needed(req) - len(shared)
                if cow is not None and needed > self._available_blocks():
                    # tight supply: the COW pin holds an evictable
                    # block hostage without reducing demand (the
                    # private copy still needs a fresh block), so
                    # drop the partial match and recompute its
                    # < block_size tokens rather than stall/fail
                    self.pool.decref(cow)
                    cow, cow_len = None, 0
                    matched = len(shared) * self.block_size
                if fail_fast or needed > self._available_blocks():
                    for b in shared:        # roll the pin back
                        self.pool.decref(b)
                    if cow is not None:
                        self.pool.decref(cow)
                    if not any(r is not None for r in self.slot_req):
                        # nothing in flight to free up blocks
                        raise ValueError(
                            f"request {req.rid}: needs "
                            f"{self._blocks_needed(req)} KV blocks "
                            f"but the pool has {self.num_blocks}; "
                            f"grow num_blocks")
                    # backpressure: wait for a harvest to free blocks
                    self.admission_stalls += 1
                    self.metrics.counter(
                        "serving.admission.stalls").inc()
                    if self.obs.enabled:
                        self.obs.event("stall", rid=req.rid,
                                       needed_blocks=needed)
                    break
                self._reserved[s] = needed
                self._reserved_total += needed
                self._slot_blocks[s] = list(shared)
                for i, b in enumerate(shared):
                    self.block_table[s, i] = b
                self._num_shared[s] = len(shared)
                self._cow_pending[s] = cow is not None
                if cow is not None:
                    self.block_table[s, len(shared)] = cow
                    self._slot_blocks[s].append(cow)
                self.total_prompt_tokens += len(req.prompt)
                self.cached_prompt_tokens += matched
                self.prefix_hits += matched > 0
                if matched:
                    # once per admitted request (a stalled queue
                    # head is looked up again on every retry)
                    self.metrics.counter("serving.prefix.hits").inc()
                    self.metrics.counter(
                        "serving.prefix.hit_tokens").inc(matched)
                queue.pop(0)
                # the pos cap stops generation at max_len - in_len tokens;
                # flag the shortfall instead of harvesting silently short
                req.truncated = len(req.prompt) + req.max_new > self.max_len
                self.slot_req[s] = req
                self.mode[s] = "prefill"
                # chunked prefill resumes at the first uncached token
                self.prompt_off[s] = matched
                self.pos[s] = matched
                self.out_len[s] = 0
                # per-slot sampling mirrors: the request's params, or
                # the server default (greedy unless sampling= was set)
                sp = req.sampling if req.sampling is not None \
                    else self.sampling
                self.samp_temp[s] = sp.temperature
                self.samp_top_k[s] = sp.top_k
                self.samp_top_p[s] = sp.top_p
                self.samp_seed[s] = sp.seed
                self.metrics.counter("serving.requests.admitted").inc()
                if self.obs.enabled:
                    self.obs.admit(req.rid, s, matched,
                                   req.truncated, step=self._step)
                    if matched:
                        self.obs.event("prefix_match", rid=req.rid,
                                       slot=s, matched_tokens=matched)

    def _check_done(self, s: int) -> None:
        # stop rule, applied after every emit (including the first token
        # from the final prefill chunk, so max_new=1 yields one token)
        req = self.slot_req[s]
        if (self.out_len[s] >= req.max_new
                or self.pos[s] >= self.max_len - 1):
            self._mark_done(s)

    def _mark_done(self, s: int) -> None:
        """Every prefill/decode -> done transition funnels through here
        so the tracer's per-request completion timestamp (t_done, the
        TPOT endpoint) lands exactly when the emitting dispatch's host
        bookkeeping observed the stop."""
        self.mode[s] = "done"
        if self.obs.enabled:
            req = self.slot_req[s]
            if req is not None:
                self.obs.finish(req.rid, int(self.out_len[s]))

    def _count_sampling_paths(self, lives: List[np.ndarray]) -> None:
        """One ``serving.sampling.path.*`` count per step, by the branch
        the sample head takes for the step's live rows."""
        flags = sampling.row_flags(self.samp_temp, self.samp_top_k,
                                   self.cfg.vocab_size)
        for live in lives:
            self.metrics.counter("serving.sampling.path."
                                 + sampling.batch_path(*flags, live)).inc()

    def _run_chunk_step(self) -> int:
        """One packed step: prefill chunks + piggybacked decodes."""
        t0 = time.perf_counter()
        B, C = self.B, self.chunk
        with self.obs.phase("serve.chunk.prepare"):
            tokens_host = np.zeros((B, C), np.int32)
            n_tokens = np.zeros(B, np.int32)
            is_decode = np.zeros(B, bool)
            emit = np.zeros(B, bool)
            for s, req in enumerate(self.slot_req):
                if req is None:
                    continue
                if self.mode[s] == "prefill":
                    off = int(self.prompt_off[s])
                    n = min(C, len(req.prompt) - off)
                    tokens_host[s, :n] = req.prompt[off:off + n]
                    n_tokens[s] = n
                    emit[s] = off + n == len(req.prompt)
                    self._ensure_blocks(s, int(self.pos[s]) + n)
                elif self.mode[s] == "decode":
                    n_tokens[s] = 1
                    is_decode[s] = True
                    emit[s] = True
                    self._ensure_blocks(s, int(self.pos[s]) + 1)
            self._count_sampling_paths([emit])
        with self.obs.phase("serve.chunk.put"):
            ops = (self._put(tokens_host), self._put(self.pos.copy()),
                   self._put(n_tokens), self._put(is_decode),
                   self._put(emit), self._put(self.out_len.copy()),
                   self._put(self.samp_temp.copy()),
                   self._put(self.samp_top_k.copy()),
                   self._put(self.samp_top_p.copy()),
                   self._put(self.samp_seed.copy()),
                   self._put(self._device_block_table()))
        t_call = time.perf_counter()
        with self.obs.phase("serve.chunk.enqueue"):
            self.cache, self.cur_tok, self.out_buf = self._chunk_fn(
                self.params, self.cache, self.cur_tok, self.out_buf, *ops)
        t_ret = time.perf_counter()
        with self.obs.phase("serve.chunk.wait"):
            self.cur_tok.block_until_ready()
        t1 = time.perf_counter()
        self._record_dispatch("chunk", t0, t_call, t_ret, t1)
        with self.obs.phase("serve.chunk.commit"):
            packed = int(n_tokens.sum())
            self.metrics.histogram("serving.chunk.occupancy").record(
                packed / (B * C) if B * C else 0.0)
            if self.obs.enabled:
                self.obs.span("chunk_dispatch", t0, t1,
                              packed_tokens=packed,
                              n_prefill=int((n_tokens > 0).sum()
                                            - is_decode.sum()),
                              n_decode=int(is_decode.sum()),
                              step=self._step)
            # EOS needs the emitted tokens on the host; length-only stopping
            # stays transfer-free (the readback is explicit so the loop
            # stays valid under jax.transfer_guard("disallow"))
            toks = (jax.device_get(self.cur_tok) if self.eos_id is not None
                    else None)
            prompt_tokens = 0
            for s, req in enumerate(self.slot_req):
                if req is None:
                    continue
                if self.mode[s] == "prefill":
                    n = int(n_tokens[s])
                    prompt_tokens += n
                    self.prompt_off[s] += n
                    self.pos[s] += n
                    if self.obs.enabled:
                        self.obs.prefill_dispatch(req.rid, t1 - t0)
                    if emit[s]:                 # prompt exhausted: first token
                        self.mode[s] = "decode"
                        self.out_len[s] += 1
                        if self.obs.enabled:
                            self.obs.first_token(req.rid, step=self._step)
                        if toks is not None and int(toks[s]) == self.eos_id:
                            self._mark_done(s)
                        else:
                            self._check_done(s)
                elif self.mode[s] == "decode":
                    self.out_len[s] += 1
                    self.pos[s] += 1
                    if self.obs.enabled:
                        self.obs.decode_dispatch(req.rid, 1, t1 - t0)
                    if toks is not None and int(toks[s]) == self.eos_id:
                        self._mark_done(s)
                    else:
                        self._check_done(s)
            return prompt_tokens

    def _run_decode_span(self) -> None:
        t0 = time.perf_counter()
        with self.obs.phase("serve.span.prepare"):
            active = np.array([m == "decode" for m in self.mode])
            max_new = np.array(
                [r.max_new if r is not None else 0
                 for r in self.slot_req], np.int32)
            # deterministic mirror of the on-device span, computed up
            # front so the paged allocator knows each slot's final
            # frontier before the device writes to it (EOS may stop a
            # slot earlier than the sim — that only over-assigns blocks
            # within the reservation)
            cap = self.max_len - 1
            sim_pos = self.pos.copy()
            sim_out = self.out_len.copy()
            sim_act = active.copy()
            lives = []
            for _ in range(self.span):
                lives.append(sim_act.copy())
                for s in np.flatnonzero(sim_act):
                    sim_out[s] += 1
                    sim_pos[s] += 1
                    if (sim_out[s] >= max_new[s] or sim_pos[s] >= cap):
                        sim_act[s] = False
            self._count_sampling_paths(lives)
            for s in np.flatnonzero(active):
                self._ensure_blocks(s, int(sim_pos[s]))
        with self.obs.phase("serve.span.put"):
            ops = (self._put(self.pos.copy()),
                   self._put(self.out_len.copy()),
                   self._put(active), self._put(max_new),
                   self._put(self.samp_temp.copy()),
                   self._put(self.samp_top_k.copy()),
                   self._put(self.samp_top_p.copy()),
                   self._put(self.samp_seed.copy()),
                   self._put(self._device_block_table()))
        t_call = time.perf_counter()
        with self.obs.phase("serve.span.enqueue"):
            (self.cache, self.cur_tok, self.out_buf, pos_d, out_d,
             act_d) = self._span_fn(self.params, self.cache, self.cur_tok,
                                    self.out_buf, *ops)
        t_ret = time.perf_counter()
        with self.obs.phase("serve.span.wait"):
            self.cur_tok.block_until_ready()
        t1 = time.perf_counter()
        self._record_dispatch("span", t0, t_call, t_ret, t1)
        with self.obs.phase("serve.span.commit"):
            prev_out = self.out_len
            if self.eos_id is None:
                self.pos = sim_pos
                self.out_len = sim_out
                done_now = active & ~sim_act
            else:
                # EOS stopping is data-dependent: sync the span's final
                # bookkeeping instead of trusting the length-only sim
                self.pos = np.array(jax.device_get(pos_d), np.int32)
                self.out_len = np.array(jax.device_get(out_d), np.int32)
                done_now = active & ~jax.device_get(act_d)
            productive = int((self.out_len - prev_out).sum())
            self.metrics.histogram("serving.span.utilization").record(
                productive / (self.B * self.span))
            if self.obs.enabled:
                self.obs.span("span_dispatch", t0, t1, steps=self.span,
                              n_active=int(active.sum()),
                              emitted=productive, step=self._step)
                step_s = (t1 - t0) / self.span
                for s in np.flatnonzero(self.out_len != prev_out):
                    k = int(self.out_len[s] - prev_out[s])
                    self.obs.decode_dispatch(self.slot_req[s].rid, k,
                                             k * step_s)
            for s in np.flatnonzero(done_now):
                self._mark_done(s)

    def _run_spec_step(self) -> None:
        """One speculative draft→verify→accept step for every decoding
        slot (runtime/spec_decode.py): up to K drafts per slot from the
        device-resident n-gram table, one fixed-shape `verify_step`
        dispatch scoring all B×(K+1) tokens, longest argmax-matching
        prefix accepted plus the bonus token from the first mismatch.
        Acceptance is data-dependent, so (unlike the length-only span
        path) the final pos/out_len/active state always syncs back;
        the paged block tables are then rolled back to each slot's
        accepted frontier."""
        t0 = time.perf_counter()
        K = self.spec_decode
        with self.obs.phase("serve.verify.prepare"):
            active = np.array([m == "decode" for m in self.mode])
            max_new = np.array(
                [r.max_new if r is not None else 0
                 for r in self.slot_req], np.int32)
            cap = self.max_len - 1
            for s in np.flatnonzero(active):
                # cover the verify window only up to the slot's emit
                # budget: the window rows past it can never be
                # accepted and their writes drop beyond the table,
                # so admission reservations (computed from max_new)
                # always suffice
                budget = min(int(max_new[s]) - int(self.out_len[s]),
                             cap - int(self.pos[s]))
                self._ensure_blocks(
                    s, int(self.pos[s]) + min(K + 1, max(budget, 1)))
        with self.obs.phase("serve.verify.put"):
            ops = (self._put(self.pos.copy()),
                   self._put(self.out_len.copy()), self._put(active),
                   self._put(max_new),
                   self._put(self.samp_temp.copy()),
                   self._put(self.samp_top_k.copy()),
                   self._put(self.samp_top_p.copy()),
                   self._put(self.samp_seed.copy()),
                   self._put(self._device_block_table()))
        t_call = time.perf_counter()
        with self.obs.phase("serve.verify.enqueue"):
            (self.cache, self.ngram_table, self.cur_tok, self.out_buf,
             pos_d, out_d, act_d, emit_d) = self._verify_fn(
                self.params, self.cache, self.ngram_table, self.cur_tok,
                self.out_buf, *ops)
        t_ret = time.perf_counter()
        with self.obs.phase("serve.verify.wait"):
            self.cur_tok.block_until_ready()
            emit = jax.device_get(emit_d)
            self.pos = np.array(jax.device_get(pos_d), np.int32)
            self.out_len = np.array(jax.device_get(out_d), np.int32)
            done_now = active & ~jax.device_get(act_d)
        t1 = time.perf_counter()
        self._record_dispatch("verify", t0, t_call, t_ret, t1)
        with self.obs.phase("serve.verify.commit"):
            # rejected drafts: shrink the block-table frontier back to
            # the accepted positions (restores the reservation drawn
            # pre-verify; stale KV beyond it is never read)
            for s in np.flatnonzero(active):
                rolled = self._truncate_blocks(s, int(self.pos[s]))
                if rolled:
                    self.metrics.counter(
                        "serving.spec.rollback_blocks").inc(rolled)
                    if self.obs.enabled:
                        self.obs.event("spec_rollback", slot=int(s),
                                       blocks=rolled)
            for s in np.flatnonzero(done_now):
                self._mark_done(s)
            if self.obs.enabled:
                for s in np.flatnonzero(emit):
                    self.obs.decode_dispatch(self.slot_req[s].rid,
                                             int(emit[s]), t1 - t0)
            nact = int(active.sum())
            self.spec_steps += 1
            self.spec_slot_steps += nact
            self.spec_drafted += K * nact
            self.spec_emitted += int(emit.sum())
            self.spec_accepted += int(np.maximum(emit - 1, 0).sum())
            spec.record_dispatch(
                self.metrics, self.obs, t0=t0, t1=t1, k=K, n_active=nact,
                emitted=int(emit.sum()),
                accepted=int(np.maximum(emit - 1, 0).sum()),
                step=self._step)

    def _record_dispatch(self, kind: str, t0: float, t_call: float,
                         t_ret: float, t1: float) -> None:
        """One dispatch's host/device split, from the stamps around it:
        method entry ``t0``, the jitted call's start ``t_call`` and
        return ``t_ret``, and ``t1`` after ``block_until_ready``.
        ``host_s + wait_s == wall_s`` holds exactly, and ``gap_s`` runs
        from the previous dispatch's ready stamp to this call: the
        device's idle time per step while no dispatch overlaps another
        (the stamps never enter a jitted body — JX001/AST001)."""
        m, phase = self.metrics, _PHASE[kind]
        host, wait = t_ret - t0, t1 - t_ret
        m.counter(f"serving.dispatches.{phase}").inc()
        m.histogram(f"serving.wall_s.{phase}").record(host + wait)
        m.histogram(f"serving.host_s.{phase}").record(host)
        m.histogram(f"serving.wait_s.{phase}").record(wait)
        if self._t_ready is not None:
            m.histogram("serving.gap_s").record(t_call - self._t_ready)
        self._t_ready = t1
        self._last_kind = kind

    @_in_phase("serve.harvest")
    def _harvest(self) -> int:
        done_slots = [s for s in range(self.B) if self.mode[s] == "done"]
        if not done_slots:
            return 0
        # gather only the finished slots' rows on device before the host
        # copy — the old path shipped the whole [B, max_len] buffer over
        # on every harvest
        rows = jax.device_get(jnp.take(
            self.out_buf, self._put(np.asarray(done_slots, np.int32)),
            axis=0))
        served = 0
        for i, s in enumerate(done_slots):
            req = self.slot_req[s]
            req.output = [int(t) for t in rows[i, : int(self.out_len[s])]]
            req.done = True
            served += len(req.prompt) + len(req.output)
            self.metrics.counter("serving.requests.harvested").inc()
            if self.obs.enabled:
                self.obs.finish(req.rid, len(req.output))
                self.obs.event("harvest", rid=req.rid, slot=s,
                               n_out=len(req.output))
            self.slot_req[s] = None
            self.mode[s] = "idle"
            if self.prefix_cache is not None:
                self._insert_prefix(s, req)
            self._free_slot_blocks(s)
        return served

    def _insert_prefix(self, s: int, req: Request) -> None:
        """Feed the finished request's full-block prefix back into the
        radix tree (before the decrefs of `_free_slot_blocks`, so newly
        adopted blocks are retained instead of freed).  The last output
        token never has KV written (it is never fed back), so the run
        covers positions [0, in_len + out_len - 1)."""
        assert not self._cow_pending[s], \
            f"slot {s}: unresolved copy-on-write at harvest"
        run = np.concatenate(
            [req.prompt, np.asarray(req.output[:-1], np.int32)])
        nfull = len(run) // self.block_size
        if nfull:
            self.prefix_cache.insert(run[:nfull * self.block_size],
                                     self._slot_blocks[s][:nfull])

    # -- main loop ---------------------------------------------------------
    def _reset_run_counters(self) -> None:
        """Per-run metric state, shared by ``serve`` / ``serve_online``
        (the tracer's event log, by contrast, accumulates across runs
        until the caller clears it — warm/measured A/B runs call
        ``tracer.clear()`` between waves)."""
        self.metrics.reset()
        self._step = 0
        self._last_kind = None
        self._t_ready = None
        # pool metrics are per run, not per server lifetime
        self.peak_blocks = self._blocks_in_use()
        self.admission_stalls = 0
        self.total_prompt_tokens = 0
        self.cached_prompt_tokens = 0
        self.prefix_hits = 0
        self._evict0 = (self.prefix_cache.evicted_blocks
                        if self.prefix_cache is not None else 0)
        if self.spec_decode:
            # spec metrics are per run too (the n-gram table persists
            # across runs — warm drafts are a feature)
            self.spec_steps = 0
            self.spec_slot_steps = 0
            self.spec_drafted = 0
            self.spec_accepted = 0
            self.spec_emitted = 0

    def serve(self, requests: List[Request]) -> Dict[str, float]:
        queue = list(requests)
        self._reset_run_counters()
        if self.obs.enabled:
            for r in queue:
                self.obs.enqueue(r.rid, len(r.prompt), r.max_new)
        t0 = time.perf_counter()
        served_tokens = 0
        prefill_tokens = 0
        while queue or any(r is not None for r in self.slot_req):
            with self.obs.phase("serve.step", step_num=self._step):
                self._admit(queue)
                if any(m == "prefill" for m in self.mode):
                    prefill_tokens += self._run_chunk_step()
                elif any(m == "decode" for m in self.mode):
                    if self.spec_decode:
                        self._run_spec_step()
                    else:
                        self._run_decode_span()
                served_tokens += self._harvest()
            self._step += 1
        dt = time.perf_counter() - t0
        return self._run_stats(requests, dt, served_tokens,
                               prefill_tokens)

    def serve_online(self, stream, *,
                     max_idle_sleep_s: float = 0.02) -> Dict[str, float]:
        """Open-loop serving: admit by arrival time against a
        monotonic clock (runtime/arrivals.py streams).

        ``stream`` is a sequence of ``TimedRequest``-shaped objects
        (``.t_arrival`` seconds from the loop epoch, ``.request`` a
        ``Request``).  The loop anchors the epoch to
        ``time.perf_counter()`` at entry and releases each request to
        the admission queue only once the clock passes its stamp, so
        the engine runs under sustained, bursty load instead of a
        pre-loaded batch; between dispatches the scheduler re-polls
        arrivals, and when fully drained with arrivals still pending
        it sleeps (host-side, capped at ``max_idle_sleep_s``) until
        the next stamp.

        Telemetry contract: the tracer's enqueue timestamp is the
        request's *scheduled arrival* (epoch + t_arrival), not the
        moment the scheduler observed it — a request arriving
        mid-dispatch is charged its queue delay (and therefore TTFT)
        from arrival.  Everything else reuses the closed-batch
        machinery verbatim: the same jitted work units (compile counts
        unchanged), the same host mirrors (a warmed loop stays clean
        under ``jax.transfer_guard("disallow")`` — the clock and the
        sleep are host-only), and greedy outputs on a ``closed_stream``
        are bit-identical to ``serve`` on the same requests.

        Each request's record also keeps when the loop released it
        into the admission queue (``t_seen``) and which dispatch the
        loop ran last before then (``seen_after``): a request that
        arrives during a dispatch waits for it to end.

        Returns the ``serve`` stats plus online extras: realized
        ``offered_rate_rps``, ``arrival_span_s``, idle/sleep seconds,
        and the peak admission-queue depth (also tracked live in the
        ``serving.queue.depth`` gauge for the windowed views).
        """
        arrivals = sorted(stream, key=lambda tr: tr.t_arrival)
        requests = [tr.request for tr in arrivals]
        self._reset_run_counters()
        queue: List[Request] = []
        served_tokens = 0
        prefill_tokens = 0
        idle_s = 0.0
        peak_queue_depth = 0
        next_i = 0
        t0 = time.perf_counter()
        while (next_i < len(arrivals) or queue
               or any(r is not None for r in self.slot_req)):
            with self.obs.phase("serve.step", step_num=self._step):
                with self.obs.phase("serve.poll"):
                    now = time.perf_counter() - t0
                    while (next_i < len(arrivals)
                           and arrivals[next_i].t_arrival <= now):
                        tr = arrivals[next_i]
                        next_i += 1
                        queue.append(tr.request)
                        if self.obs.enabled:
                            self.obs.enqueue(
                                tr.request.rid, len(tr.request.prompt),
                                tr.request.max_new, t=t0 + tr.t_arrival,
                                t_seen=t0 + now,
                                seen_after=self._last_kind)
                    depth = len(queue)
                    peak_queue_depth = max(peak_queue_depth, depth)
                    self.metrics.gauge("serving.queue.depth").set(
                        float(depth))
                self._admit(queue)
                if any(m == "prefill" for m in self.mode):
                    prefill_tokens += self._run_chunk_step()
                elif any(m == "decode" for m in self.mode):
                    if self.spec_decode:
                        self._run_spec_step()
                    else:
                        self._run_decode_span()
                elif not queue and next_i < len(arrivals):
                    # fully drained with arrivals still scheduled: sleep
                    # toward the next stamp instead of busy-spinning
                    with self.obs.phase("serve.sleep"):
                        wait = (t0 + arrivals[next_i].t_arrival
                                - time.perf_counter())
                        if wait > 0:
                            nap = min(wait, max_idle_sleep_s)
                            time.sleep(nap)
                            idle_s += nap
                served_tokens += self._harvest()
            self._step += 1
        dt = time.perf_counter() - t0
        stats = self._run_stats(requests, dt, served_tokens,
                                prefill_tokens)
        span_s = arrivals[-1].t_arrival if arrivals else 0.0
        stats.update({
            "online": 1.0,
            "arrival_span_s": float(span_s),
            "offered_rate_rps": (len(arrivals) / span_s
                                 if span_s > 0 else 0.0),
            "idle_s": idle_s,
            "peak_queue_depth": float(peak_queue_depth),
        })
        return stats

    def _run_stats(self, requests: List[Request], dt: float,
                   served_tokens: int, prefill_tokens: int
                   ) -> Dict[str, float]:
        compiles = self.compile_counts()
        # phase counts/wall times come from the metrics registry the
        # dispatch methods feed (obs/metrics) — the registry is always
        # live, so these stats keys survive with or without a tracer
        m = self.metrics
        chunk_steps = m.counter_value("serving.dispatches.prefill")
        span_disp = m.counter_value("serving.dispatches.span")
        verify_disp = m.counter_value("serving.dispatches.verify")
        prefill_s = m.hist_total("serving.wall_s.prefill")
        span_s = m.hist_total("serving.wall_s.span")
        verify_s = m.hist_total("serving.wall_s.verify")
        stats = {
            "requests": float(len(requests)),
            "tokens": float(served_tokens),
            "seconds": dt,
            "tokens_per_s": served_tokens / dt if dt > 0 else 0.0,
            "prefill_seconds": prefill_s,
            "decode_seconds": span_s + verify_s,
            "verify_seconds": verify_s,
            "prefill_tokens": float(prefill_tokens),
            "decode_tokens": float(sum(len(r.output) for r in requests)),
            "decode_steps": float(span_disp * self.span),
            "chunk_steps": float(chunk_steps),
            "decode_spans": float(span_disp + verify_disp),
            "compiled_programs": float(sum(max(v, 0)
                                           for v in compiles.values())),
            "tp": float(self.tp),
        }
        if self.spec_decode:
            stats.update({
                "spec_k": float(self.spec_decode),
                "spec_steps": float(self.spec_steps),
                "spec_drafted_tokens": float(self.spec_drafted),
                "spec_accepted_tokens": float(self.spec_accepted),
                "spec_acceptance_rate": (
                    self.spec_accepted / self.spec_drafted
                    if self.spec_drafted else 0.0),
                # mean emitted tokens per slot per verify dispatch; the
                # span loop's equivalent is exactly 1.0
                "spec_tokens_per_step": (
                    self.spec_emitted / self.spec_slot_steps
                    if self.spec_slot_steps else 0.0),
            })
        kv_bytes = sum(int(leaf.nbytes) for leaf in
                       jax.tree_util.tree_leaves(self.cache))
        stats.update({
            "pool_blocks": float(self.num_blocks),
            "block_size": float(self.block_size),
            "peak_blocks_in_use": float(self.peak_blocks),
            "pool_utilization": (self.peak_blocks / self.num_blocks
                                 if self.num_blocks else 0.0),
            "kv_tokens_capacity": float(self.num_blocks
                                        * self.block_size),
            "admission_stalls": float(self.admission_stalls),
            # the pool shards its KV-head dim over the tp mesh, so
            # every device holds all blocks but only KH/tp heads
            "kv_bytes_per_device": float(kv_bytes // self.tp),
        })
        if self.prefix_cache is not None:
            total = self.total_prompt_tokens
            stats.update({
                "prefix_cache_enabled": 1.0,
                "prompt_tokens_total": float(total),
                "prefix_cached_tokens": float(
                    self.cached_prompt_tokens),
                "cached_token_fraction": (
                    self.cached_prompt_tokens / total if total
                    else 0.0),
                "prefix_hit_requests": float(self.prefix_hits),
                "prefix_hit_rate": (self.prefix_hits / len(requests)
                                    if requests else 0.0),
                "cache_evictions": float(
                    self.prefix_cache.evicted_blocks - self._evict0),
                "cached_blocks": float(
                    self.prefix_cache.cached_block_count()),
            })
        return stats


# the engine under its short name (examples and tests import it)
Server = ChunkedServer
