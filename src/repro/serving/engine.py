"""Batched serving engine with a paged KV cache — the §2.2 TLB in action.

The engine owns one physical page pool for K and one for V, each stacked
over the layers and lane-dense, (L, P, page, Hkv*D); each request's logical
(virtual) cache pages are mapped to physical pages through a page table.
The jitted programs take the pools donated and carry them whole through the
layer loop, so every write lands in place and nothing copies a layer's
pages out of the stack.
Page allocation goes through buffer *registration* on an RdmaEndpoint
(core/rdma): the first touch of a page walks the "Nios II" path, later
accesses hit the hardware TLB — the engine reports the measured hit rate
and the modelled Fig 2 bandwidth gain alongside throughput.

Decode attention dispatches through kernels/ops.paged_attention: on TPU
the Pallas kernel translates pages inside its BlockSpec index_map (the
hardware TLB); under GSPMD/CPU the XLA gather path runs (the software
walk).  Continuous batching: finished requests free their pages; admitted
requests prefill into freshly mapped ones.

Engine scope: decoder-only transformer families (dense/moe/vlm).

The serving loop marks its work with profiler spans (``span``), on the
same clock as the device trace: ``engine/step`` around each engine step
and, inside it, each slot claim, prefill chunk, decode dispatch, token
readback and slot release, with the request, slot and step they serve as
arguments.  The jitted decode and prefill-chunk programs name their parts
with ``jax.named_scope`` (``embed``; per layer ``qkv``, ``kv_write``,
``attention``, ``attn_out``, ``mlp``; ``head``), which the device ops
carry in their metadata.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import fabric
from repro.core.apelink import NetModel
from repro.core.rdma import RdmaEndpoint
from repro.core.tlb import PAGE_BYTES
from repro.core.topology import Torus
from repro.kernels import ops
from repro.models import attention as attn_mod
from repro.models import common
from repro.models import moe as moe_mod
from repro.models import transformer
from repro.models.common import ArchCfg

SPAN_PREFIX = "engine/"
SPANS = ("step", "claim", "prefill_chunk", "decode", "sample", "retire")


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """The profiler span ``engine/<name>`` (one of ``SPANS``) carrying
    ``args``; about a microsecond when no profiler is recording."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **args)


class TruncatedRunError(RuntimeError):
    """``run_to_completion`` exhausted ``max_steps`` with requests still
    in flight.  Returning silently here would quietly truncate exactly
    the tail of a long replay — the p99 requests are the ones still in
    flight — so the driver raises and carries the evidence."""

    def __init__(self, steps: int, in_flight: int) -> None:
        super().__init__(
            f"run_to_completion truncated after {steps} steps with "
            f"{in_flight} request(s) still in flight (raise max_steps, "
            "or drain the admission queue)")
        self.steps = steps
        self.in_flight = in_flight


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray           # (S,) int32
    max_new_tokens: int
    out_tokens: list[int] = dataclasses.field(default_factory=list)
    slot: int | None = None
    pos: int = 0                 # current context length
    # -- trace-replay / SLO surface (all optional; the engine never
    #    requires them).  Times are on the cluster's shared fabric
    #    timeline (seconds); ``warm_tokens`` is the prefix the node's
    #    modelled prefix cache already holds (a session follow-up routed
    #    to its home node skips that much prefill compute — modelled
    #    accounting only, the real prefill path ignores it).
    arrival_s: float | None = None
    admit_s: float | None = None       # left the admission queue
    first_token_s: float | None = None  # end of the window that produced
    #                                     the first output token (TTFT)
    finish_s: float | None = None
    shed_s: float | None = None        # admission gave up (SLO shed)
    warm_tokens: int = 0
    session: int = -1                  # trace session id (-1: none)
    submitted: float | None = None     # perf_counter at Engine.submit

    @property
    def done(self) -> bool:
        return len(self.out_tokens) >= self.max_new_tokens


class PageAllocator:
    """Free-list page allocator whose pages are TLB-registered buffers."""

    def __init__(self, n_pages: int, page_tokens: int, bytes_per_token: int,
                 endpoint: RdmaEndpoint) -> None:
        self.free = list(range(n_pages - 1, -1, -1))
        self.page_tokens = page_tokens
        self.endpoint = endpoint
        self.region = endpoint.register(
            max(n_pages * page_tokens * bytes_per_token, PAGE_BYTES))
        self.translation_cost = 0.0

    def alloc(self) -> int:
        if not self.free:
            raise RuntimeError("page pool exhausted")
        page = self.free.pop()
        # translating the page's address range = registration fast/slow path
        vaddr = self.region.vaddr + page * PAGE_BYTES
        _, cost = self.endpoint.tlb.translate(vaddr)
        self.translation_cost += cost
        return page

    def release(self, pages: list[int]) -> None:
        self.free.extend(pages)

    @property
    def hit_rate(self) -> float:
        return self.endpoint.tlb.stats.hit_rate


@dataclasses.dataclass
class SlotState:
    """A running slot's exportable KV state — what a migration moves.

    ``k``/``v`` hold only the slot's LIVE pages (the ones covering
    ``seq_len`` tokens) in page-table (logical) order, shaped like the
    pool with its page axis cut to them, (L, n_pages, page_tokens,
    n_kv_heads * head_dim) — the zero/stale
    ``max_new`` headroom pages never touch the wire; the importer claims
    all ``n_alloc`` pages fresh from its own pool (physical page ids are
    a node-local detail and do NOT travel).

    A *modelled* node (``PagedLM(modelled=True)``) exports ``k = v =
    None`` with ``n_live`` carrying the page count: the wire payload is
    priced identically, only the tensor contents are absent.
    """

    k: jax.Array | None
    v: jax.Array | None
    seq_len: int
    page_tokens: int
    n_alloc: int                 # total pages the importer must claim
    nbytes: int                  # wire payload (live page contents only)
    n_live: int = -1             # live page count when k is None

    @property
    def n_pages(self) -> int:
        """Live pages on the wire (<= n_alloc)."""
        if self.k is None:
            return int(self.n_live)
        return int(self.k.shape[1])


class PagedLM:
    """Decode wrapper holding paged K/V pools for every layer.

    ``torus``/``rank`` place this node's fabric twin at its real torus
    coordinate (a serving cluster passes the shared cluster fabric);
    ``tp_axes`` are the mesh axes of the modelled tensor-parallel
    deployment — default: one axis per torus dimension; pass ``()`` for a
    single-card replica whose fabric traffic is only inter-node
    (migration) RDMA.

    ``modelled=True`` keeps the whole control plane — slots, page
    allocator, TLB registration, export/import, RDMA endpoint — but
    allocates no K/V tensors and compiles no kernels: decode/prefill
    become pure accounting (tokens are placeholders, compute is priced
    analytically by the window owner).  This is what lets a 512-node
    trace replay drive the real router/admission/migration machinery
    without 512 live model replicas.
    """

    def __init__(self, cfg: ArchCfg, params, *, max_batch: int,
                 max_seq: int, page_tokens: int = 16,
                 pool_pages: int | None = None,
                 torus: Torus | None = None,
                 tp_axes: tuple[str, ...] | None = None,
                 rank: int = 0, net: NetModel | None = None,
                 sim: fabric.FabricSim | None = None,
                 cost_backend: str = "analytic",
                 cost_fidelity: str = "packet",
                 descriptor_bytes: float | None = None,
                 modelled: bool = False) -> None:
        assert cfg.family in ("dense", "moe", "vlm")
        self.cfg = cfg
        self.params = params
        self.modelled = modelled
        self.page = page_tokens
        self.max_batch = max_batch
        self.pages_per_seq = -(-max_seq // page_tokens)
        need = max_batch * self.pages_per_seq
        self.n_pages = pool_pages or int(need * 1.25)
        hd = cfg.resolved_head_dim
        L = cfg.n_layers
        # a page's K (or V) for every KV head, one lane-dense row per token:
        # for qwen2-0.5b a page is one bf16 (16, 128) tile, so the layer
        # loop, its scatters and gathers and the kernel share one layout
        self.pool_shape = (L, self.n_pages, page_tokens, cfg.n_kv_heads * hd)
        if modelled:
            self.k_pool = None
            self.v_pool = None
        else:
            self.k_pool = jnp.zeros(self.pool_shape, cfg.dtype)
            self.v_pool = jnp.zeros_like(self.k_pool)
        self.page_table = np.zeros((max_batch, self.pages_per_seq), np.int32)
        self.seq_lens = np.zeros((max_batch,), np.int32)
        self.torus = torus or Torus((4, 4))
        self.rank = rank
        if not 0 <= rank < self.torus.size:
            raise ValueError(f"rank {rank} out of range for torus "
                             f"{self.torus.dims}")
        self.net = net or NetModel()
        self.bytes_per_token = 2 * L * cfg.n_kv_heads * hd * 2
        # shared fabric timeline: a serving cluster passes ONE simulator
        # (any fidelity tier of ``fabric.make_sim`` — packet ``FabricSim``,
        # ``FluidSim`` or ``HybridSim``; the surface is duck-typed) so
        # this node's migration PUTs and decode-step TP collectives contend
        # with every other node's traffic on the same torus links
        self.sim = sim
        self.endpoint = RdmaEndpoint(self.torus, rank=rank, net=self.net,
                                     sim=sim,
                                     descriptor_bytes=descriptor_bytes)
        self.allocator = PageAllocator(
            self.n_pages, page_tokens,
            bytes_per_token=self.bytes_per_token, endpoint=self.endpoint)
        # Fabric twin of a TP deployment of this model on the torus: one
        # residual-stream all-reduce per layer per decode step, priced by
        # the same CollectiveSchedule the trainer executes.  Reported in
        # stats() against the measured decode step time.
        if tp_axes is None:   # one TP axis per torus dim, whatever its rank
            names = ("x", "y", "z")
            tp_axes = tuple(names[i] if i < len(names) else f"d{i}"
                            for i in range(self.torus.ndims))
        self.tp_axes = tuple(tp_axes)
        self._cost_backend = cost_backend
        self._cost_fidelity = cost_fidelity
        if self.tp_axes:
            self.tp_schedule = fabric.lower_all_reduce(self.torus,
                                                       self.tp_axes)
            ar_bytes = max_batch * cfg.d_model * jnp.dtype(cfg.dtype).itemsize
            # per-decode-step TP wire bytes: one residual all-reduce per
            # layer (the per-step traffic a shared sim injects as flows)
            self.tp_step_bytes = L * ar_bytes
            self._tp_base = self.tp_schedule   # healthy-fabric lowering
            self._tp_ar_bytes = ar_bytes
            self.predicted_tp_comm_s = L * fabric.estimate(
                self.tp_schedule, ar_bytes, self.net,
                backend=cost_backend, fidelity=cost_fidelity).total_s
        else:
            self.tp_schedule = None
            self._tp_base = None
            self._tp_ar_bytes = 0
            self.tp_step_bytes = 0
            self.predicted_tp_comm_s = 0.0
        self.slot_pages: dict[int, list[int]] = {}
        self.steps = 0               # decode steps run (``engine/decode``)
        # the programs update the pools (arguments 2 and 3) in place; a
        # modelled lm never calls them, so they never compile
        pools = dict(donate_argnums=(2, 3))
        self._decode = jax.jit(self._decode_impl, **pools)
        self._prefill = jax.jit(self._prefill_impl, **pools)
        self._prefill_chunk = jax.jit(self._prefill_chunk_impl, **pools)

    # -- fault feed -------------------------------------------------------------
    def relower_tp(self, faults) -> bool:
        """Re-lower the decode TP twin through ``fabric.rewrite`` against
        the cluster's fault map, so the per-step TP flows the engine
        injects price shrunk/detoured rings honestly (a dead link on the
        TP ring becomes explicit detour hops in the schedule, not just a
        sim-side route resolution).  Returns True when the twin changed.

        A fault map that partitions the TP ring is unroutable; the last
        routable twin is kept — the sim's own BFS keeps detouring what it
        can, and the cluster surfaces the partition on the paths that
        genuinely need the dead links."""
        if self._tp_base is None:
            return False
        try:
            sched = fabric.rewrite(self._tp_base, faults) if faults \
                else self._tp_base
        except fabric.UnroutableError:
            return False
        if sched == self.tp_schedule:
            return False
        self.tp_schedule = sched
        self.predicted_tp_comm_s = self.cfg.n_layers * fabric.estimate(
            sched, self._tp_ar_bytes, self.net,
            backend=self._cost_backend,
            fidelity=self._cost_fidelity).total_s
        return True

    # -- slot management --------------------------------------------------------
    def _claim(self, npages: int) -> int:
        """Claim a free slot holding ``npages`` freshly allocated pages."""
        if npages > self.pages_per_seq:
            # ValueError, NOT RuntimeError: admission retries RuntimeError
            # (transient exhaustion), but an oversize request can never
            # fit and must fail loudly instead of re-queueing forever
            raise ValueError(
                f"request needs {npages} pages > pages_per_seq "
                f"{self.pages_per_seq} (raise max_seq or shorten it)")
        used = set(self.slot_pages)
        slot = next((i for i in range(self.max_batch) if i not in used),
                    None)
        if slot is None:
            raise RuntimeError("no free decode slot")
        pages: list[int] = []
        try:
            for _ in range(npages):
                pages.append(self.allocator.alloc())
        except Exception:
            # pool exhausted mid-claim: hand the partial allocation back so
            # admission can retry cleanly once pages free up (a leak here
            # permanently shrinks the pool)
            self.allocator.release(pages)
            raise
        self.slot_pages[slot] = pages
        self.page_table[slot, :npages] = pages
        self.seq_lens[slot] = 0
        return slot

    def claim_slot(self, prompt_len: int, max_new: int) -> int:
        return self._claim(-(-(prompt_len + max_new) // self.page))

    def free_slot(self, slot: int) -> None:
        self.allocator.release(self.slot_pages.pop(slot))
        self.seq_lens[slot] = 0

    # -- slot migration (export/import) -----------------------------------------
    def live_pages(self, slot: int) -> list[int]:
        """The slot's pages actually covering its ``seq_len`` tokens — the
        only ones a migration must move (headroom pages hold no state the
        decode can ever read: positions past seq_len are masked)."""
        seq_len = int(self.seq_lens[slot])
        n_live = min(len(self.slot_pages[slot]), -(-seq_len // self.page))
        return self.slot_pages[slot][:n_live]

    def export_slot(self, slot: int) -> SlotState:
        """Snapshot a slot's live KV pages (logical order) + seq_len."""
        live = np.asarray(self.live_pages(slot), np.int32)
        if self.modelled:
            # no tensor contents to snapshot — the wire payload (and the
            # importer's page claim) are priced from the counts alone
            return SlotState(
                k=None, v=None,
                seq_len=int(self.seq_lens[slot]), page_tokens=self.page,
                n_alloc=len(self.slot_pages[slot]), n_live=len(live),
                nbytes=len(live) * self.page * self.bytes_per_token)
        return SlotState(
            k=self.k_pool[:, live], v=self.v_pool[:, live],
            seq_len=int(self.seq_lens[slot]), page_tokens=self.page,
            n_alloc=len(self.slot_pages[slot]),
            nbytes=len(live) * self.page * self.bytes_per_token)

    def import_slot(self, state: SlotState) -> int:
        """Land a migrated slot: claim ``n_alloc`` local pages, write the
        live KV contents, restore the sequence length.  Decode resumes
        bitwise-identically — the live page contents and seq_len are the
        whole decode state (headroom content is never read before being
        written)."""
        if state.page_tokens != self.page:
            raise ValueError(
                f"page_tokens mismatch: exported {state.page_tokens}, "
                f"local {self.page}")
        if state.n_pages > state.n_alloc:
            raise ValueError(f"corrupt slot state: {state.n_pages} live "
                             f"pages > {state.n_alloc} allocated")
        slot = self._claim(state.n_alloc)
        if state.n_pages and not self.modelled and state.k is not None:
            live = jnp.asarray(self.slot_pages[slot][:state.n_pages],
                               jnp.int32)
            self.k_pool = self.k_pool.at[:, live].set(state.k)
            self.v_pool = self.v_pool.at[:, live].set(state.v)
        self.seq_lens[slot] = state.seq_len
        return slot

    # -- jitted compute ----------------------------------------------------------
    def _prefill_impl(self, params, tokens, k_pool, v_pool, page_table,
                      slot, true_len):
        """Prefill one request's prompt into its pages (batch of 1).

        tokens are right-padded to a page multiple; the returned logits are
        taken at the *true* last prompt position."""
        cfg = self.cfg
        _, cache, h = transformer.prefill(cfg, params, {"tokens": tokens},
                                          max_len=tokens.shape[1],
                                          remat=False, return_hidden=True,
                                          moe_dropless=True)
        S = tokens.shape[1]
        last_h = jax.lax.dynamic_slice_in_dim(h, true_len - 1, 1, axis=1)
        logits = common.lm_head(cfg, params["embed"], last_h)
        k = cache["k"][:, 0]   # (L, S, Hkv, hd)
        v = cache["v"][:, 0]
        npage_prompt = S // self.page   # S is padded to page multiple
        kp = k.reshape(cfg.n_layers, npage_prompt, self.page, -1)
        vp = v.reshape(cfg.n_layers, npage_prompt, self.page, -1)
        dest = jax.lax.dynamic_slice(page_table, (slot, 0),
                                     (1, self.pages_per_seq))[0]
        k_pool = k_pool.at[:, dest[:npage_prompt]].set(kp)
        v_pool = v_pool.at[:, dest[:npage_prompt]].set(vp)
        return logits[:, -1], k_pool, v_pool

    def _prefill_chunk_impl(self, params, tokens, k_pool, v_pool,
                            page_table, slot, start_pos, n_alloc):
        """Prefill ONE page-aligned chunk of a prompt (batch of 1).

        The overlap engine's serving analogue: instead of one monolithic
        prompt forward stalling the running decode batch, the prompt is
        admitted in page-sized chunks interleaved with decode steps.  Each
        chunk writes its K/V into the slot's pages and attends all cached
        positions <= its own (causal over the page span), so the math per
        query is identical to the whole-prompt prefill.

        tokens: (1, T) with T a page multiple (final chunk right-padded);
        start_pos: absolute position of tokens[0, 0] (page-aligned);
        n_alloc: pages claimed for the slot — padded-chunk writes past the
        allocation are dropped (their queries are padding, never read).
        Returns (logits (1, T, V), k_pool, v_pool)."""
        cfg = self.cfg
        T = tokens.shape[1]
        npage = T // self.page
        hd = cfg.resolved_head_dim
        group = cfg.n_heads // cfg.n_kv_heads
        S_all = self.pages_per_seq * self.page
        with jax.named_scope("embed"):
            h = common.embed_tokens(params["embed"], tokens)
        freqs = common.rope_freqs(cfg)
        pos = start_pos + jnp.arange(T)
        page0 = start_pos // self.page
        rows = jax.lax.dynamic_slice(page_table, (slot, 0),
                                     (1, self.pages_per_seq))[0]

        def body(carry, xs):
            h, kp, vp = carry
            lp, layer = xs
            with jax.named_scope("qkv"):
                x = common.apply_norm(cfg, lp["ln1"], h)
                q, k, v = attn_mod._project_qkv(cfg, lp["attn"], x, x)
                q = common.apply_rope(q, pos[None], freqs)
                k = common.apply_rope(k, pos[None], freqs)
            with jax.named_scope("kv_write"):
                dest = jax.lax.dynamic_slice(rows, (page0,), (npage,))
                dest = jnp.where(page0 + jnp.arange(npage) < n_alloc, dest,
                                 self.n_pages)
                kp = kp.at[layer, dest].set(
                    k[0].reshape(npage, self.page, -1), mode="drop")
                vp = vp.at[layer, dest].set(
                    v[0].reshape(npage, self.page, -1), mode="drop")
            with jax.named_scope("attention"):
                kd = kp[layer, rows].reshape(S_all, cfg.n_kv_heads, hd)
                vd = vp[layer, rows].reshape(S_all, cfg.n_kv_heads, hd)
                qf = q[0].astype(jnp.float32) * hd ** -0.5
                kf = kd.astype(jnp.float32)
                vf = vd.astype(jnp.float32)
                if group > 1:
                    kf = jnp.repeat(kf, group, axis=1)
                    vf = jnp.repeat(vf, group, axis=1)
                logits = jnp.einsum("qhd,khd->hqk", qf, kf)
                mask = jnp.arange(S_all)[None, :] <= pos[:, None]
                logits = jnp.where(mask[None], logits, -jnp.inf)
                probs = jax.nn.softmax(logits, axis=-1)
                out = jnp.einsum("hqk,khd->qhd", probs, vf)
            with jax.named_scope("attn_out"):
                a = out.astype(h.dtype).reshape(1, T, -1) @ lp["attn"]["wo"]
                h = h + a
            with jax.named_scope("mlp"):
                x2 = common.apply_norm(cfg, lp["ln2"], h)
                if cfg.moe is not None:
                    m, _ = moe_mod.apply_moe(cfg, lp["moe"], x2,
                                             dropless=True)
                else:
                    m = common.apply_mlp(cfg, lp["mlp"], x2)
                h = h + m
            return (h, kp, vp), None

        (h, k_pool, v_pool), _ = jax.lax.scan(
            body, (h, k_pool, v_pool),
            (params["layers"], jnp.arange(cfg.n_layers)))
        with jax.named_scope("head"):
            h = common.apply_norm(cfg, params["final_norm"], h)
            logits = common.lm_head(cfg, params["embed"], h)
        return logits, k_pool, v_pool

    def _decode_impl(self, params, tokens, k_pool, v_pool, page_table,
                     seq_lens, active):
        """One batched decode step over all active slots.

        tokens: (B, 1); seq_lens: (B,) current context length per slot;
        active: (B,) bool mask."""
        cfg = self.cfg
        B = tokens.shape[0]
        with jax.named_scope("embed"):
            h = common.embed_tokens(params["embed"], tokens)
        freqs = common.rope_freqs(cfg)
        pos = seq_lens  # (B,)

        def body(carry, xs):
            h, kp, vp = carry
            lp, layer = xs
            with jax.named_scope("qkv"):
                x = common.apply_norm(cfg, lp["ln1"], h)
                q, k, v = attn_mod._project_qkv(cfg, lp["attn"], x, x)
                q = common.apply_rope(q, pos[:, None], freqs)
                k = common.apply_rope(k, pos[:, None], freqs)
            # scatter this step's K/V into each slot's current page;
            # inactive slots scatter out-of-bounds (dropped — their pages
            # may already belong to a newly admitted request)
            with jax.named_scope("kv_write"):
                page_idx = pos // self.page
                page_off = pos % self.page
                phys = jnp.take_along_axis(page_table, page_idx[:, None],
                                           axis=1)[:, 0]
                phys = jnp.where(active, phys, self.n_pages)
                kp = kp.at[layer, phys, page_off].set(k[:, 0].reshape(B, -1),
                                                      mode="drop")
                vp = vp.at[layer, phys, page_off].set(v[:, 0].reshape(B, -1),
                                                      mode="drop")
            with jax.named_scope("attention"):
                out = ops.paged_attention(q[:, 0], kp, vp, page_table,
                                          seq_lens + 1, layer=layer)
            with jax.named_scope("attn_out"):
                a = out.reshape(B, 1, -1) @ lp["attn"]["wo"]
                h = h + a
            with jax.named_scope("mlp"):
                x2 = common.apply_norm(cfg, lp["ln2"], h)
                if cfg.moe is not None:
                    m, _ = moe_mod.apply_moe(cfg, lp["moe"], x2,
                                             dropless=True)
                else:
                    m = common.apply_mlp(cfg, lp["mlp"], x2)
                h = h + m
            return (h, kp, vp), None

        (h, k_pool, v_pool), _ = jax.lax.scan(
            body, (h, k_pool, v_pool),
            (params["layers"], jnp.arange(cfg.n_layers)))
        with jax.named_scope("head"):
            h = common.apply_norm(cfg, params["final_norm"], h)
            logits = common.lm_head(cfg, params["embed"], h)[:, 0]
            logits = jnp.where(active[:, None], logits, 0.0)
        return logits, k_pool, v_pool

    # -- public API ---------------------------------------------------------------
    def prefill_slot(self, slot: int, prompt: np.ndarray) -> int:
        pad = (-len(prompt)) % self.page
        tokens = jnp.asarray(
            np.pad(prompt, (0, pad))[None].astype(np.int32))
        # NOTE: padded prompt tokens are attended (right padding); the
        # first generated token comes from the true last prompt position,
        # so we prefill only up to len(prompt) and ignore tail positions by
        # setting seq_len to the true length.
        logits, self.k_pool, self.v_pool = self._prefill(
            self.params, tokens, self.k_pool, self.v_pool,
            jnp.asarray(self.page_table), slot, len(prompt))
        self.seq_lens[slot] = len(prompt)
        return int(jnp.argmax(logits[0]))

    def prefill_slot_chunk(self, slot: int, prompt: np.ndarray, start: int,
                           chunk_tokens: int) -> int | None:
        """Prefill ``prompt[start:start+chunk_tokens]`` into the slot.

        ``start`` and ``chunk_tokens`` must be page multiples.  Returns the
        first generated token when the chunk covers the prompt tail (the
        request is then decode-ready), else None."""
        if start % self.page or chunk_tokens % self.page:
            raise ValueError("chunk boundaries must be page-aligned")
        end = min(start + chunk_tokens, len(prompt))
        toks = np.zeros((chunk_tokens,), np.int32)
        toks[:end - start] = prompt[start:end]
        logits, self.k_pool, self.v_pool = self._prefill_chunk(
            self.params, jnp.asarray(toks[None]), self.k_pool, self.v_pool,
            jnp.asarray(self.page_table), slot, start,
            len(self.slot_pages[slot]))
        if end < len(prompt):
            return None
        self.seq_lens[slot] = len(prompt)
        return int(jnp.argmax(logits[0, len(prompt) - 1 - start]))

    def decode_batch(self, tokens: np.ndarray, active: np.ndarray):
        """One decode step: dispatch the decode program (``engine/decode``),
        then read each slot's next token back to the host
        (``engine/sample``, where the host waits on the device)."""
        n = int(active.sum())
        with span("decode", step=self.steps, tokens=n):
            logits, self.k_pool, self.v_pool = self._decode(
                self.params, jnp.asarray(tokens[:, None].astype(np.int32)),
                self.k_pool, self.v_pool, jnp.asarray(self.page_table),
                jnp.asarray(self.seq_lens), jnp.asarray(active))
        self.seq_lens = self.seq_lens + active.astype(np.int32)
        with span("sample", step=self.steps, tokens=n):
            out = np.asarray(jnp.argmax(logits, -1))
        self.steps += 1
        return out


class Engine:
    """Continuous-batching loop over a PagedLM.

    ``chunked_prefill=True`` admits prompts in page-sized chunks
    interleaved with decode steps (one chunk per prefilling request per
    engine step), so a long prompt no longer stalls the running batch for
    its whole forward — the serving-side overlap engine.  Tokens are
    identical to whole-prompt prefill (same per-query attention math).

    ``decode_stall_s`` counts host time in admission and chunk dispatch
    (with the final chunk's readback) while a decode batch waits, not the
    device time the batch waits behind the chunks.
    """

    def __init__(self, lm: PagedLM, *, chunked_prefill: bool = False,
                 prefill_chunk_pages: int = 1) -> None:
        self.lm = lm
        self.chunked_prefill = chunked_prefill
        self.chunk_tokens = max(prefill_chunk_pages, 1) * lm.page
        self.pending: list[Request] = []
        self.prefilling: dict[int, Request] = {}
        self.running: dict[int, Request] = {}
        self.finished: list[Request] = []
        self.steps = 0
        self.prefill_chunks = 0
        # host seconds of steps that admitted or prefilled while a decode
        # batch was running: ``_admit`` and chunk dispatch, with the final
        # chunk's token readback.  Chunks run asynchronously, so this is
        # not the device time the batch waited behind them: that shows in
        # the profiler trace, as chunk programs between ``engine/sample``
        # spans (the benchmark's ``itl_tail_chunk_share``).
        self.decode_stall_s = 0.0
        # shared-timeline accounting (lm.sim attached): each decode step
        # injects the node's TP collective traffic as flows; the timeline
        # owner (the serving cluster) settles them per logical window
        self.pending_comm_fids: list[int] = []
        self.sim_tp_comm_s = 0.0    # settled, contention-priced TP comm
        self.sim_comm_steps = 0
        # per-window SLO accounting, consumed (and cleared) by the
        # cluster's window close: which requests produced their first
        # token / finished this window, and how much compute the window
        # carried (decode tokens everywhere; cold prefill tokens only on
        # a modelled lm — the real prefill path measures itself)
        self.window_first: list[Request] = []
        self.window_finished: list[Request] = []
        self.window_decode_tokens = 0
        self.window_cold_prefill_tokens = 0

    @property
    def load(self) -> int:
        """Requests this engine is responsible for (the router's metric)."""
        return len(self.pending) + len(self.prefilling) + len(self.running)

    def submit(self, req: Request) -> None:
        req.submitted = time.perf_counter()
        self.pending.append(req)

    # -- migration hooks (ServingCluster) ---------------------------------------
    def detach(self, slot: int) -> Request:
        """Hand a running request over to a migration (its pages stay
        claimed until the cluster frees them after the PUT)."""
        return self.running.pop(slot)

    def attach(self, req: Request) -> None:
        """Adopt a migrated request whose slot was already imported."""
        if req.slot is None or req.slot in self.running:
            raise ValueError(f"cannot attach request {req.rid} at slot "
                             f"{req.slot}")
        self.running[req.slot] = req

    def _admit(self) -> int:
        admitted = 0
        while self.pending and len(self.running) + len(self.prefilling) \
                < self.lm.max_batch:
            req = self.pending.pop(0)
            waited_ms = (time.perf_counter() - req.submitted) * 1e3 \
                if req.submitted is not None else -1.0
            try:
                with (contextlib.nullcontext() if self.lm.modelled else
                      span("claim", rid=req.rid, waited_ms=waited_ms)) \
                        as claim:
                    slot = self.lm.claim_slot(len(req.prompt),
                                              req.max_new_tokens)
                    if claim is not None:
                        claim.set_metadata(slot=slot)
            except (RuntimeError, StopIteration):
                self.pending.insert(0, req)
                return admitted
            except ValueError:
                # oversize request: surface the error, but keep the request
                # addressable (it must not vanish from every queue)
                self.pending.insert(0, req)
                raise
            req.slot = slot
            admitted += 1
            if self.chunked_prefill:
                req.pos = 0
                self.prefilling[slot] = req
            else:
                if self.lm.modelled:
                    # accounting-only prefill: a session follow-up on its
                    # home node skips the warm prefix (modelled prefix
                    # cache); the cold remainder is charged to the window
                    warm = min(max(req.warm_tokens, 0), len(req.prompt))
                    self.window_cold_prefill_tokens += \
                        len(req.prompt) - warm
                    self.lm.seq_lens[slot] = len(req.prompt)
                    first = 0
                else:
                    first = self.lm.prefill_slot(slot, req.prompt)
                req.out_tokens.append(first)
                req.pos = len(req.prompt)
                self.running[slot] = req
                self.window_first.append(req)
        return admitted

    def _advance_prefills(self) -> int:
        """One page-sized chunk per prefilling request per engine step."""
        chunks = 0
        if self.lm.modelled:
            return self._advance_prefills_modelled()
        for slot, req in list(self.prefilling.items()):
            last = int(req.pos + self.chunk_tokens >= len(req.prompt))
            with span("prefill_chunk", rid=req.rid, slot=slot,
                      start=req.pos, last=last):
                tok = self.lm.prefill_slot_chunk(slot, req.prompt, req.pos,
                                                 self.chunk_tokens)
            self.prefill_chunks += 1
            chunks += 1
            req.pos = min(req.pos + self.chunk_tokens, len(req.prompt))
            if tok is not None:
                req.out_tokens.append(tok)
                req.pos = len(req.prompt)
                del self.prefilling[slot]
                self.running[slot] = req
                self.window_first.append(req)
        return chunks

    def _advance_prefills_modelled(self) -> int:
        """Accounting-only chunked prefill: the warm prefix (home-node
        prefix-cache hit) is skipped outright, each step charges one
        chunk of the cold remainder to ``window_cold_prefill_tokens``,
        and the request goes decode-ready when the cursor covers the
        prompt — same admission cadence as the real chunked path."""
        chunks = 0
        for slot, req in list(self.prefilling.items()):
            if req.pos == 0 and req.warm_tokens > 0:
                req.pos = min(req.warm_tokens, len(req.prompt))
            end = min(req.pos + self.chunk_tokens, len(req.prompt))
            self.window_cold_prefill_tokens += end - req.pos
            req.pos = end
            self.prefill_chunks += 1
            chunks += 1
            if req.pos >= len(req.prompt):
                self.lm.seq_lens[slot] = len(req.prompt)
                req.out_tokens.append(0)
                req.pos = len(req.prompt)
                del self.prefilling[slot]
                self.running[slot] = req
                self.window_first.append(req)
        return chunks

    def step(self) -> None:
        with span("step", step=self.steps, pending=len(self.pending),
                  prefilling=len(self.prefilling),
                  running=len(self.running)):
            self._step()

    def _step(self) -> None:
        t0 = time.perf_counter()
        # fresh window accounting: the cluster steps each engine exactly
        # once per logical window and reads these at window close
        self.window_first = []
        self.window_finished = []
        self.window_decode_tokens = 0
        self.window_cold_prefill_tokens = 0
        had_batch = bool(self.running)
        worked = self._admit()
        if self.chunked_prefill:
            worked += self._advance_prefills()
        if had_batch and worked:
            # host time of admission and prefill with a decode batch
            # waiting: a whole-prompt prefill (which reads its token back),
            # or the dispatch of this step's chunks and the final chunk's
            # readback.  Steps that admitted or prefilled nothing did no
            # non-decode work — the _admit walk itself is not counted.
            self.decode_stall_s += time.perf_counter() - t0
        if not self.running:
            return
        if self.lm.modelled:
            self._step_modelled()
            return
        B = self.lm.max_batch
        tokens = np.zeros((B,), np.int32)
        active = np.zeros((B,), bool)
        for slot, req in self.running.items():
            tokens[slot] = req.out_tokens[-1]
            active[slot] = not req.done
        self.window_decode_tokens += int(active.sum())
        nxt = self.lm.decode_batch(tokens, active)
        if self.lm.sim is not None and self.lm.tp_schedule is not None:
            # this step's TP collectives enter the shared timeline at the
            # current window start, tagged DECODE: on a QoS fabric the
            # link arbiter protects them from concurrent BULK migrations;
            # they are settled (and priced, WITH whatever traffic they
            # contended against) by settle_comm
            self.pending_comm_fids.extend(fabric.inject_schedule(
                self.lm.sim, self.lm.tp_schedule, self.lm.tp_step_bytes,
                start_s=self.lm.sim.now, granularity="phase",
                cls=fabric.TrafficClass.DECODE))
            self.sim_comm_steps += 1
        self.steps += 1
        for slot, req in self.running.items():
            if active[slot]:
                req.out_tokens.append(int(nxt[slot]))
                req.pos += 1
        done = [slot for slot, req in self.running.items() if req.done]
        if done:
            with span("retire", finished=len(done)):
                for slot in done:
                    self.lm.free_slot(slot)
                    req = self.running.pop(slot)
                    self.finished.append(req)
                    self.window_finished.append(req)

    def _step_modelled(self) -> None:
        """Decode step on a modelled lm: token bookkeeping only (the
        placeholder token is 0), same batch/finish semantics as the real
        path; the window owner prices ``window_decode_tokens`` of compute
        analytically.  TP flows still enter the shared timeline — the
        fabric twin is real even when the FLOPs are modelled."""
        for slot, req in list(self.running.items()):
            if not req.done:
                req.out_tokens.append(0)
                req.pos += 1
                self.lm.seq_lens[slot] += 1
                self.window_decode_tokens += 1
            if req.done:
                self.lm.free_slot(slot)
                self.finished.append(self.running.pop(slot))
                self.window_finished.append(req)
        if self.lm.sim is not None and self.lm.tp_schedule is not None:
            self.pending_comm_fids.extend(fabric.inject_schedule(
                self.lm.sim, self.lm.tp_schedule, self.lm.tp_step_bytes,
                start_s=self.lm.sim.now, granularity="phase",
                cls=fabric.TrafficClass.DECODE))
            self.sim_comm_steps += 1
        self.steps += 1

    def settle_comm(self, window_start: float) -> float:
        """Resolve this window's injected TP flows against the shared
        timeline; accrues their contention-priced wall time and returns
        the window's comm end (``window_start`` when idle).  Called by
        the timeline owner (the serving cluster) once per logical window."""
        if not self.pending_comm_fids:
            return window_start
        sim = self.lm.sim
        sim.run()
        end = max(sim.finish_s(f) for f in self.pending_comm_fids)
        self.pending_comm_fids = []
        self.sim_tp_comm_s += max(end - window_start, 0.0)
        return end

    def run_to_completion(self, max_steps: int = 10_000) -> None:
        steps = 0
        while (self.pending or self.prefilling or self.running) \
                and steps < max_steps:
            self.step()
            steps += 1
        if self.pending or self.prefilling or self.running:
            raise TruncatedRunError(steps, self.load)

    def stats(self) -> dict:
        alloc = self.lm.allocator
        return {
            "decode_steps": self.steps,
            "finished": len(self.finished),
            "tlb_hit_rate": alloc.hit_rate,
            "translation_cost_s": alloc.translation_cost,
            # fabric CollectiveSchedule prediction: the per-step TP
            # all-reduce cost a torus deployment would add
            "predicted_tp_comm_s": self.lm.predicted_tp_comm_s,
            # overlap engine (serving side): chunked-prefill admission
            "chunked_prefill": self.chunked_prefill,
            "prefill_chunks": self.prefill_chunks,
            "decode_stall_s": self.decode_stall_s,
            # shared-timeline contention pricing (0.0 without a sim): TP
            # comm as actually experienced against concurrent traffic,
            # vs predicted_tp_comm_s which prices a quiet fabric
            "sim_tp_comm_s": self.sim_tp_comm_s,
            "sim_comm_steps": self.sim_comm_steps,
        }
