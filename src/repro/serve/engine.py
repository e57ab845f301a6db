"""Continuous-batching serving engine over the paged KV pool.

vLLM-style serving loop for the split-deployment server half
(ROADMAP item 1): requests arrive at any time, are admitted into decode
slots as soon as a slot AND their full page reservation are available,
and retire the moment they hit EOS or their token budget — their pages
return to the pool immediately, so a long request never stalls short
ones and short ones never pay the longest request's latency.

One engine ``step()`` is: retire -> admit (+ batched prefill of the
admissions) -> one decode tick over every active slot.  Prefill runs as
its own batched forward (``serve/decode.prefill`` on a bucketed shape),
so admission never recompiles or stalls the in-flight decode step; the
prefilled ring caches are scattered into the paged pools by
``paged.insert_prefill`` (pools donated, in-place).  The prefill is given
each row's last real position and returns the logits there only,
(rows, V): the host copies back one vocabulary row per admitted request
(and per dummy row that pads the wave to a power of two), never the
(rows, bucket, V) logits of every position.

Split-serve mode (``split_wire=QuantConfig(...)``): the client is assumed
to hold the vision tower + connector; the engine runs the connector
client-side, ships the connector activations through the existing wire
codec (``core/quantizers`` encode -> decode, the PR-3/6 machinery), feeds
the reconstruction to the server prefill via the ``image_features``
bypass, and accounts the payload bytes in ``stats['wire_bytes']`` —
matching ``WireLink.fwd_wire_bytes`` static accounting.  A grouped
``split_wire`` (non-empty ``group_widths``) ships the connector
activations as a mixed-precision ``GroupedPayload``;
``split_wire_budget_bits`` additionally re-plans the widths between
prefills from a per-channel entropy EMA of the connector features.

Tracing: each phase of ``step()`` runs inside a
``jax.profiler.TraceAnnotation`` (``engine.step`` around ``engine.admit``,
``engine.prefill.inputs``, ``engine.wire``, ``engine.prefill.launch``,
``engine.prefill.fetch``, ``engine.tick.inputs``, ``engine.tick.launch``,
``engine.tick.fetch``, ``engine.pick`` and ``engine.emit``), which records
only while a profiler session runs, on the clock of the device trace.
``*.launch`` only dispatches; ``*.fetch`` is where the host waits for the
device and copies the logits back; ``engine.prefill.fetch`` carries the
bytes it copies.  ``stats`` counts the prefill rows, positions computed
and positions that hold a prompt, the bytes of logits the prefills copied
back (``prefill_fetch_bytes``), and the programs lowered
(``compiles``); ``Request`` keeps its submit and admit times on
the clock of ``arrival_time`` and ``emit_times``.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ArchConfig
from repro.core import quantizers
from repro.core.quantizers import QuantConfig
from repro.kernels import attention_ops, decode_kernel
from repro.models import transformer as tf
from repro.models.layers.mlp import mlp_forward
from repro.serve import decode as sd
from repro.serve import paged
from repro.serve.pool import PagePool
from repro.serve.scheduler import Request, SlotScheduler
from repro.utils import compile_cache

__all__ = ["ServeEngine"]


class ServeEngine:
    """Slot-based continuous-batching engine (single host, one model)."""

    def __init__(self, params, cfg: ArchConfig, *, n_slots: int,
                 page_size: int, n_pages: int,
                 window: Optional[int] = None, temperature: float = 0.0,
                 eos_id: Optional[int] = None, seed: int = 0,
                 split_wire: Optional[QuantConfig] = None,
                 split_wire_budget_bits: Optional[float] = None,
                 split_plan_groups: int = 8,
                 lora_adapters=None, lora_scale: float = 1.0,
                 weight_quant: Optional[str] = None, wq_group: int = 128,
                 wq_act_order: bool = False,
                 wq_calib: Optional[Dict] = None):
        if cfg.modality == "audio":
            raise NotImplementedError("engine serves text/vlm configs")
        if attention_ops.resolve_impl() == "pallas":
            attention_ops.require_compiled(
                decode_kernel.paged_compiles(page_size),
                f"paged decode with page_size {page_size}")
        if lora_adapters is not None:
            # SplitLoRA serving: fold the adapters into the base weights
            # ONCE at construction (merge == apply bit-exactly, so merged
            # decoding is token-exact vs the unmerged forward) — steady
            # state serving pays zero adapter overhead per token.
            from repro.peft import merge_lora
            params = merge_lora(params, lora_adapters, scale=lora_scale)
        self.wq_report = None
        if weight_quant is not None:
            # Weight-only serving quantization (ROADMAP item 5): replace
            # every structural w* matmul site in the stacks with a packed
            # int4/int3 store AFTER the LoRA merge (the adapters must fold
            # into the dense weights before they are frozen into codes).
            # With a calibration batch the quantizer runs GPTQ error
            # compensation off per-site Hessians; without one it falls
            # back to round-to-nearest.
            from repro import wq
            wcfg = wq.parse_weight_quant(weight_quant, group=wq_group,
                                         act_order=wq_act_order)
            hessians = None
            if wq_calib is not None:
                hessians = wq.collect_hessians(params, cfg, wq_calib,
                                               window=window)
            params, self.wq_report = wq.quantize_params(params, wcfg,
                                                        hessians=hessians)
        self.params = params
        self.cfg = cfg
        self.page_size = page_size
        self.window = window
        self.temperature = temperature
        self.eos_id = eos_id
        self.split_wire = split_wire
        # entropy-adaptive split wire: re-plan the connector link's
        # channel order + per-group widths between prefills, budgeted at
        # ``split_wire_budget_bits`` mean code bits per shipped scalar
        # (bucket-size independent — the byte budget scales with the
        # payload).  The plan lives on ``split_wire.group_widths`` /
        # ``.channel_perm``, so the codec and the byte accounting pick
        # it up unchanged.  Sorted grouping matters here: connector
        # channels are strongly heterogeneous, and entropy-ranked groups
        # let the allocator starve the near-dead ones.
        self.split_wire_budget_bits = split_wire_budget_bits
        self.split_plan_groups = split_plan_groups
        self._wire_ema = None
        if split_wire_budget_bits is not None:
            if split_wire is None:
                raise ValueError("split_wire_budget_bits needs split_wire")
            from repro.core import entropy as entropy_mod
            self._wire_ema = entropy_mod.init_entropy_ema(cfg.d_model)
        self.pools = paged.init_pools(cfg, n_pages, page_size)
        self.page_pool = PagePool(n_pages)
        n_img = cfg.n_image_tokens if cfg.modality == "vlm" else 0
        self.n_image_tokens = n_img
        self.scheduler = SlotScheduler(n_slots, self.page_pool, page_size,
                                       n_image_tokens=n_img)
        self._step_fn = paged.compiled_paged_step(cfg, window=window)
        self._rng = jax.random.PRNGKey(seed)
        self._next_rid = 0
        self.stats = dict(wire_bytes=0, prefill_batches=0, decode_ticks=0,
                          tokens_emitted=0, admitted=0, retired=0,
                          prefill_rows=0, prefill_positions=0,
                          prefill_real_positions=0,
                          prefill_fetch_bytes=0, compiles=0,
                          page_table_buckets=set())
        self._lowerings0 = compile_cache.lowerings()
        if self.wq_report is not None:
            self.stats["weight_bytes_dense"] = sum(
                d for d, _ in self.wq_report.values())
            self.stats["weight_bytes_packed"] = sum(
                p for _, p in self.wq_report.values())

    # -- request intake -------------------------------------------------
    def submit(self, tokens: List[int], *, max_new: int,
               image_embeds=None, arrival_time: float = 0.0) -> int:
        if max_new < 1:
            raise ValueError("max_new must be >= 1")
        if self.cfg.modality == "vlm" and image_embeds is None:
            raise ValueError("vlm configs require image_embeds per request")
        rid = self._next_rid
        self._next_rid += 1
        self.scheduler.submit(Request(rid=rid, tokens=list(tokens),
                                      max_new=max_new,
                                      image_embeds=image_embeds,
                                      arrival_time=arrival_time,
                                      submit_time=time.perf_counter()))
        return rid

    @property
    def idle(self) -> bool:
        return self.scheduler.idle

    def request(self, rid: int) -> Request:
        return self.scheduler.requests[rid]

    # -- sampling -------------------------------------------------------
    def _pick(self, last_logits: np.ndarray) -> np.ndarray:
        """(m, V) -> (m,) token ids (greedy, or temperature sampling)."""
        with TraceAnnotation("engine.pick"):
            if self.temperature <= 0.0:
                return np.argmax(last_logits, axis=-1)
            self._rng, sub = jax.random.split(self._rng)
            return np.asarray(jax.random.categorical(
                sub, jnp.asarray(last_logits) / self.temperature, axis=-1))

    def _maybe_finish(self, req: Request, tok: int) -> None:
        if self.eos_id is not None and tok == self.eos_id:
            self.scheduler.retire(req, "eos")
        elif len(req.out) >= req.max_new:
            self.scheduler.retire(req, "length")
        if req.state == "done":
            self.stats["retired"] += 1

    # -- prefill (admission batch) --------------------------------------
    def _ship_image_features(self, image_embeds: jnp.ndarray) -> jnp.ndarray:
        """Client-side connector -> quantized wire -> server-side
        reconstruction, with payload byte accounting.

        With a grouped ``split_wire`` the payload is a
        :class:`~repro.core.payload.GroupedPayload` (per-group codes at
        per-group widths); ``wire_bytes`` stays exact either way.  In
        adaptive mode the connector features first advance the entropy
        EMA and may re-plan the widths for THIS and later shipments.
        """
        import dataclasses

        feats = mlp_forward(self.params["connector"],
                            image_embeds.astype(tf.cdtype(self.cfg)))
        if self.split_wire_budget_bits is not None:
            from repro.core import entropy as entropy_mod
            from repro.launch import schedules

            self._wire_ema = entropy_mod.update_entropy_ema(self._wire_ema,
                                                            feats)
            d = feats.shape[-1]
            perm, plan = schedules.replan_grouped(
                self._wire_ema,
                self.split_wire_budget_bits * feats.size / 8.0,
                n_groups=self.split_plan_groups,
                scalars_per_channel=feats.size // d)
            if (plan != self.split_wire.group_widths
                    or perm != self.split_wire.channel_perm):
                self.split_wire = dataclasses.replace(self.split_wire,
                                                      group_widths=plan,
                                                      channel_perm=perm)
                self.stats["wire_plan"] = plan
        payload = quantizers.encode(self.split_wire, feats)
        self.stats["wire_bytes"] += payload.wire_bytes()
        return quantizers.decode(self.split_wire, payload)

    def _prefill(self, admitted: List[Request]) -> None:
        cfg, pg = self.cfg, self.page_size
        n_img = self.n_image_tokens
        plens = [len(r.tokens) for r in admitted]
        # bucket the prefill shape: pow2 page count for the ring length,
        # pow2 row count — bounded set of compiled prefill shapes.
        npb = paged.next_pow2(-(-(n_img + max(plens)) // pg))
        lb = npb * pg
        rows = paged.next_pow2(len(admitted))
        lp = lb - n_img  # token length such that positions cover exactly lb
        with TraceAnnotation("engine.prefill.inputs", rows=rows,
                             positions=rows * lb):
            tokens = np.zeros((rows, lp), np.int32)
            for i, r in enumerate(admitted):
                tokens[i, :len(r.tokens)] = r.tokens
            batch: Dict = dict(tokens=jnp.asarray(tokens))
            if cfg.modality == "vlm":
                batch["image_embeds"] = jnp.asarray(np.stack(
                    [np.asarray(r.image_embeds) for r in admitted]
                    + [np.zeros_like(np.asarray(admitted[0].image_embeds))]
                    * (rows - len(admitted))))
            # scatter the ring caches into each request's physical pages;
            # logical pages past a row's reservation (and the dummy rows)
            # go to the trash page, right-padding is masked to pos = -1.
            page_rows = np.zeros((rows, npb), np.int32)
            valid_len = np.zeros((rows,), np.int32)
            for i, r in enumerate(admitted):
                row = (r.pages + [0] * npb)[:npb]
                page_rows[i] = row
                valid_len[i] = n_img + plens[i]
            # the first emitted token is picked at each row's LAST REAL
            # position (never the pad tail's); dummy rows read position 0
            last_pos = jnp.asarray(np.maximum(valid_len - 1, 0))
            page_rows, valid_len = (jnp.asarray(page_rows),
                                    jnp.asarray(valid_len))
        if "image_embeds" in batch and self.split_wire is not None:
            # popped, so the embeddings are freed once they are shipped
            with TraceAnnotation("engine.wire") as span:
                sent = self.stats["wire_bytes"]
                batch["image_features"] = self._ship_image_features(
                    batch.pop("image_embeds"))
                span.set_metadata(wire_bytes=self.stats["wire_bytes"] - sent)
        with TraceAnnotation("engine.prefill.launch"):
            self._rng, prefill_rng = jax.random.split(self._rng)
            logits, caches = sd.prefill(self.params, cfg, batch, lb,
                                        window=self.window, rng=prefill_rng,
                                        last_positions=last_pos)
            self.pools = paged.insert_prefill(self.pools, caches, page_rows,
                                              valid_len)
        with TraceAnnotation("engine.prefill.fetch", bytes=logits.nbytes):
            last = np.asarray(logits)[:len(admitted)]
        self.stats["prefill_fetch_bytes"] += logits.nbytes
        toks = self._pick(last)
        with TraceAnnotation("engine.emit"):
            now = time.perf_counter()
            for r, tok in zip(admitted, toks):
                r.out.append(int(tok))
                r.emit_times.append(now)
                self.stats["tokens_emitted"] += 1
                self._maybe_finish(r, int(tok))
        self.stats["prefill_batches"] += 1
        self.stats["admitted"] += len(admitted)
        self.stats["prefill_rows"] += rows
        self.stats["prefill_positions"] += rows * lb
        self.stats["prefill_real_positions"] += sum(n_img + p for p in plens)

    # -- decode tick ----------------------------------------------------
    def _decode_tick(self, active: List[Request]) -> None:
        pg = self.page_size
        s = self.scheduler.n_slots
        npp = paged.next_pow2(max(r.qpos // pg + 1 for r in active))
        self.stats["page_table_buckets"].add(npp)
        with TraceAnnotation("engine.tick.inputs", active=len(active),
                             npp=npp):
            tokens = np.zeros((s, 1), np.int32)
            qpos = np.full((s,), -1, np.int32)
            page_table = np.full((s, npp), -1, np.int32)
            for r in active:
                tokens[r.slot, 0] = r.out[-1]
                qpos[r.slot] = r.qpos
                row = r.pages[:npp]
                page_table[r.slot, :len(row)] = row
            tokens, qpos, page_table = (jnp.asarray(tokens),
                                        jnp.asarray(qpos),
                                        jnp.asarray(page_table))
        with TraceAnnotation("engine.tick.launch"):
            logits, self.pools = self._step_fn(
                self.params, self.pools, dict(tokens=tokens), qpos,
                page_table)
        with TraceAnnotation("engine.tick.fetch"):
            last = np.asarray(logits)[:, -1]
        toks = self._pick(last)
        with TraceAnnotation("engine.emit"):
            now = time.perf_counter()
            for r in active:
                tok = int(toks[r.slot])
                r.out.append(tok)
                r.qpos += 1
                r.emit_times.append(now)
                self.stats["tokens_emitted"] += 1
                self._maybe_finish(r, tok)
        self.stats["decode_ticks"] += 1

    # -- main loop ------------------------------------------------------
    def step(self) -> None:
        """One engine tick: admit (+ prefill) then decode every slot."""
        with TraceAnnotation("engine.step"):
            with TraceAnnotation("engine.admit") as span:
                admitted = self.scheduler.admit()
                span.set_metadata(admitted=len(admitted))
            if admitted:
                self._prefill(admitted)
            active = self.scheduler.active
            if active:
                self._decode_tick(active)
            self.stats["compiles"] = (compile_cache.lowerings()
                                      - self._lowerings0)

    def run(self) -> Dict[int, List[int]]:
        """Drive until every submitted request finished."""
        while not self.idle:
            before = (self.stats["tokens_emitted"], len(self.scheduler.waiting))
            self.step()
            after = (self.stats["tokens_emitted"], len(self.scheduler.waiting))
            if before == after:  # no progress: pool can never fit the head
                head = self.scheduler.waiting[0]
                raise RuntimeError(
                    f"request {head.rid} needs "
                    f"{self.scheduler.pages_needed(head)} pages but the "
                    f"pool only has {self.page_pool.n_pages - 1}")
        return {rid: r.out for rid, r in self.scheduler.requests.items()}
