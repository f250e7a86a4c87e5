"""Federated training orchestration — paper Algorithm 1 end-to-end.

Phases (paper §3.2):
  0. K-means clustering of clients on local-data statistics.
  1. Supervised fine-tuning (SFT), federated, instance-norm front end.
  2. DPO alignment on preference pairs (server-side, post-SFT).
  3. Forecasting fine-tuning, federated, RevIN front end.

Only LoRA adapters cross the "network"; every round's traffic is metered by
``repro.core.comm`` (C5) in the configured wire format.

Wire emulation (``REPRO_FED_WIRE``, or the ``wire=`` argument): each
client's uploaded adapter delta passes through
``repro.dist.fedcomm.quantize_update`` — the same int8/bf16 encode +
error-feedback residual the mesh ring collective uses — so Algorithm 1
aggregates exactly what the wire delivers, the residual is carried
per-client between rounds (quantization noise does not bias the paper's
aggregation), and ``comm.fedtime_round(..., wire=...)`` prices what was
actually sent.  The default f32 wire is the identity.

Fault tolerance (``repro.fault``): the round loop is deadline-bounded and
survives client churn.

  * ``fault_plan=`` injects deterministic faults (crash-before-upload,
    hang, transient-fail-then-retry with backoff, corrupt/NaN delta,
    byzantine-scaled delta, delay) on a virtual clock — no ``time.sleep``
    anywhere; the legacy ``slow_clients={id: seconds}`` kwarg is a thin
    shim over a delay-only plan.
  * ``deadline_s=`` cuts each (round, cluster) aggregation window after
    that many virtual seconds: the server aggregates the partial cohort
    with weights renormalized to sum to 1 over exactly the applied
    uploads (``ClusterServer.apply_deltas``), and a deadline-skipped
    client's EF residual carries to its next participation, so its
    quantization error is never lost.
  * Late uploads land in a server-side ``StalenessBuffer`` and apply at
    the cluster's next window down-weighted by ``staleness_decay**s``;
    at or beyond ``staleness_limit`` rounds they are rejected — bounded
    staleness, so the round clock is set by the deadline, not by the
    slowest client.
  * Every upload is validated before aggregation (``repro.fault.guard``):
    non-finite deltas reject as ``corrupt``, norm outliers as
    ``byzantine`` — zero NaN/corrupt deltas ever reach FedAdam.
  * ``secure_aggregation=True`` composes with dropout: masks are
    committed against the started cohort, and the server re-cancels the
    dropped clients' pairwise masks (``repro.core.secure_agg``) — exact,
    bit for bit, on the int8 secure wire (``wire="int8"``), approximate
    in f32.  Late uploads cannot buffer in secure mode (masks bind to
    their round's cohort); they count as dropouts.
  * ``snapshot_path=`` writes an atomic round-state snapshot after every
    (round, cluster) aggregation — adapters + FedAdam moments, EF
    residuals, staleness buffer, participation clock, RNG counters,
    virtual clock; ``resume=True`` restores it and continues the same
    round bit-identically after a kill-9 (deterministic timelines, i.e.
    ``fault_plan.base_fit_s`` set or no deadline).

Every rejection/retry/timeout/recovery emits through ``repro.obs``:
``fault.*`` / ``fed.reject`` / ``fed.deadline_miss`` instants,
``fed.rejected.<reason>`` counters, fleet-ledger ``reason`` fields, and
flight-recorder distress dumps when a round loses most of its cohort.

Per-round telemetry (``repro.obs``, ``REPRO_TRACE=0`` disables): each
(round, cluster) gets a ``fed.round`` span wrapping per-client
``fed.client_fit`` spans on a per-cluster Perfetto track; the quantized
wire's EF residual norm lands in per-client gauges + a
``fed.ef_residual_norm`` histogram (drift of carried quantization error),
the round-over-round aggregated-adapter movement in per-cluster
``fed.adapter_delta_norm.cluster<c>`` gauges + counter tracks (the
convergence signal heterogeneous-client work diagnoses stragglers
against), and the metered comm in ``fed.wire_bytes`` /
``fed.round_loss.cluster<c>``.

Fleet ledger (always on — one dataclass append per client fit): every fit
lands a :class:`repro.obs.fleet.ClientRecord` (wall time, wire bytes,
EF-residual norm, adapter-delta norm, staleness) in
``FedResult.fleet``; excluded clients are recorded with
``participated=False`` and a ``reason`` (crash/hang/deadline/corrupt/
byzantine/stale) so exclusion is auditable, and the participation clock
keeps aging them.  The ledger's per-cluster summed wire bytes equal
``comm.fedtime_round(...).bytes_up`` exactly, counting ONLY clients whose
upload actually arrived in that window — each contributes precisely
``comm.wire_payload_bytes(count_params(adapters), wire)``, the same
single source every other view of the number reads (the PR 5/6 "one
number" invariant, now five ways).  ``fleet_out=`` (or
``REPRO_FLEET_OUT``) writes the standalone ``fleet.json``.
"""

from __future__ import annotations

import dataclasses
import math
import os
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ModelConfig
from repro.core import comm, dpo, fedtime
from repro.core.client import local_update
from repro.core.clustering import cluster_clients
from repro.core.lora import (FAMILY_TARGETS, attach_lora, lora_tree,
                             merge_lora, quantize_base, trainable_fraction)
from repro.core.server import BufferedDelta, ClusterServer, StalenessBuffer
from repro.data.federated import client_weights
from repro.fault import (Attempt, FaultPlan, VirtualClock, load_round_state,
                         save_round_state, validate_deltas)
from repro.optim.fedadam import fedavg


@dataclasses.dataclass
class RoundLog:
    round: int
    cluster: int
    train_loss: float
    comm: comm.RoundStats


@dataclasses.dataclass
class FedResult:
    adapters_per_cluster: list
    base_params: dict
    logs: List[RoundLog]
    assignments: np.ndarray
    trainable_frac: float
    fleet: Optional[obs.FleetLedger] = None

    def total_megabytes(self) -> float:
        return sum(l.comm.megabytes for l in self.logs)

    def params_for_cluster(self, c: int) -> dict:
        return merge_lora(self.base_params, self.adapters_per_cluster[c])


def _stack_batches(x: np.ndarray, y: np.ndarray, steps: int, batch: int,
                   seed: int) -> dict:
    rng = np.random.default_rng(seed)
    sel = rng.integers(0, len(x), (steps, batch))
    return {"x": jnp.asarray(x[sel]), "y": jnp.asarray(y[sel])}


def _tree_delta(new, old):
    return jax.tree.map(
        lambda a, g: a.astype(jnp.float32) - g.astype(jnp.float32), new, old)


def _flatten_tree(tree):
    leaves, tdef = jax.tree.flatten(tree)
    shapes = [l.shape for l in leaves]
    splits = np.cumsum([int(np.prod(s)) if s else 1 for s in shapes])[:-1]
    flat = np.concatenate([np.asarray(l, np.float32).reshape(-1)
                           for l in leaves])
    return flat, (tdef, shapes, splits)


def _unflatten_tree(flat, spec):
    tdef, shapes, splits = spec
    parts = np.split(np.asarray(flat, np.float32), splits)
    return jax.tree.unflatten(
        tdef, [jnp.asarray(p.reshape(s)) for p, s in zip(parts, shapes)])


# ---------------------------------------------------------------------------
# Round-state snapshot plumbing (repro.fault.snapshot)
# ---------------------------------------------------------------------------

def _write_snapshot(path, *, r, c, rounds, clock, rng, servers,
                    wire_residuals, ledger, logs, buffer):
    arrays = {
        "servers": {str(i): {"adapters": s.adapters,
                             "m": s.opt["m"], "v": s.opt["v"]}
                    for i, s in enumerate(servers)},
        "residuals": {str(k): v for k, v in wire_residuals.items()
                      if v is not None},
        "buffer": {str(i): e.delta for i, e in enumerate(buffer.entries)},
    }
    meta = {
        "round": r, "cluster": c, "rounds_total": rounds,
        "clock": clock.now(),
        "rng": rng.bit_generator.state,
        "server_rounds": [s.round for s in servers],
        "last_round": {str(k): v for k, v in ledger._last_round.items()},
        "records": [rec.to_dict() for rec in ledger.records],
        "logs": [[l.round, l.cluster, l.train_loss, l.comm.bytes_up,
                  l.comm.bytes_down, l.comm.messages, l.comm.time_s]
                 for l in logs],
        "buffer": [{"client": e.client, "cluster": e.cluster,
                    "origin_round": e.origin_round, "ready_at": e.ready_at,
                    "weight": e.weight, "loss": e.loss}
                   for e in buffer.entries],
    }
    save_round_state(path, arrays, meta)


def _restore_snapshot(path, *, servers, wire_residuals, ledger, logs,
                      buffer, rng, clock):
    meta, arrays = load_round_state(path)
    srv = arrays.get("servers", {})
    for i, s in enumerate(servers):
        sd = srv[str(i)]
        s.adapters = sd["adapters"]
        s.opt = {"m": sd["m"], "v": sd["v"]}
        s.round = int(meta["server_rounds"][i])
    wire_residuals.clear()
    wire_residuals.update({int(k): v
                           for k, v in arrays.get("residuals", {}).items()})
    ledger._last_round.update({int(k): int(v)
                               for k, v in meta["last_round"].items()})
    for d in meta["records"]:
        extra = d.pop("extra", None) or {}
        ledger.records.append(obs.ClientRecord(
            d["round"], d["cluster"], d["client"], wall_s=d["wall_s"],
            wire_bytes=d["wire_bytes"], ef_norm=d["ef_norm"],
            delta_norm=d["delta_norm"], staleness=d["staleness"],
            participated=d["participated"], extra=extra or None))
    for (r_, c_, loss, up, down, msgs, t) in meta["logs"]:
        logs.append(RoundLog(int(r_), int(c_), float(loss),
                             comm.RoundStats(int(up), int(down),
                                             int(msgs), float(t))))
    deltas = arrays.get("buffer", {})
    buffer.entries = [
        BufferedDelta(int(bm["client"]), int(bm["cluster"]),
                      int(bm["origin_round"]), float(bm["ready_at"]),
                      float(bm["weight"]), float(bm["loss"]),
                      deltas[str(i)])
        for i, bm in enumerate(meta["buffer"])]
    rng.bit_generator.state = meta["rng"]
    clock.advance_to(meta["clock"])
    return int(meta["round"]), int(meta["cluster"])


def federated_fit(cfg: ModelConfig, client_data, *, rounds: int = 5,
                  batch_size: int = 16, key=None, phase: str = "forecast",
                  loss_fn: Optional[Callable] = None,
                  base_params: Optional[dict] = None,
                  init_adapters: Optional[dict] = None,
                  straggler_prob: float = 0.0,
                  secure_aggregation: bool = False,
                  wire: Optional[str] = None,
                  slow_clients: Optional[Dict[int, float]] = None,
                  fault_plan: Optional[FaultPlan] = None,
                  deadline_s: Optional[float] = None,
                  staleness_limit: int = 2,
                  staleness_decay: float = 0.5,
                  byzantine_norm_k: float = 25.0,
                  snapshot_path: Optional[str] = None,
                  resume: bool = False,
                  fleet_out: Optional[str] = None,
                  progress: Optional[Callable[[str], None]] = None
                  ) -> FedResult:
    """client_data: list of (x (n,L,M), y (n,T,M)) per client."""
    from repro.core import secure_agg
    from repro.dist import fedcomm
    ft = cfg.fedtime
    wire = wire or comm.wire_format()
    key = key if key is not None else jax.random.PRNGKey(0)
    k_init, k_lora, k_cl = jax.random.split(key, 3)

    M = client_data[0][0].shape[-1]
    if base_params is None:
        base_params = fedtime.init(cfg, k_init, num_channels=M)
    targets = FAMILY_TARGETS["dense"]
    params = attach_lora(base_params, k_lora, rank=ft.lora_rank,
                         alpha=ft.lora_alpha, targets=targets)
    if ft.qlora:
        params = quantize_base(params, qblock=ft.qlora_block,
                               targets=targets)
    if init_adapters is not None:
        params = merge_lora(params, init_adapters)   # warm start (phase hand-off)
    frac = trainable_fraction(params)
    adapters0 = lora_tree(params)

    # --- step 0: K-means clustering (paper Algorithm 1, line 3) ---
    series = [np.asarray(x).reshape(-1, x.shape[-1] * x.shape[-2])[:256]
              for x, _ in client_data]
    assign, _, _ = cluster_clients(series, ft.num_clusters, key=k_cl)
    assign = np.asarray(assign)
    weights_all = client_weights(client_data)

    if loss_fn is None:
        def loss_fn(p, batch):  # noqa: F811
            return fedtime.loss(p, cfg, batch, phase=phase)

    # legacy slow_clients kwarg: a delay-only FaultPlan on the virtual
    # clock (no time.sleep — straggler tests run in milliseconds)
    plan = fault_plan
    if plan is None and slow_clients:
        plan = FaultPlan.from_slow_clients(slow_clients)

    servers = [ClusterServer(adapters0) for _ in range(ft.num_clusters)]
    logs: List[RoundLog] = []
    rng = np.random.default_rng(7)
    clock = VirtualClock()
    buffer = StalenessBuffer(limit=staleness_limit, decay=staleness_decay)
    wire_residuals: dict = {}     # client -> flat EF residual across rounds
    ledger = obs.FleetLedger()
    secure_int = secure_aggregation and wire == "int8"
    secure_step = secure_agg.default_step()
    _, flat_spec = _flatten_tree(adapters0)   # shared secure-wire layout
    # the per-client upload: same single source fedtime_round prices, so
    # the ledger's per-cluster sums match stats.bytes_up exactly
    client_wire_bytes = comm.wire_payload_bytes(
        comm.count_params(adapters0), wire)

    resume_after = None
    if resume:
        if not snapshot_path:
            raise ValueError("resume=True needs snapshot_path")
        resume_after = _restore_snapshot(
            snapshot_path, servers=servers, wire_residuals=wire_residuals,
            ledger=ledger, logs=logs, buffer=buffer, rng=rng, clock=clock)
        obs.instant("fed.resume", cat="fault", round=resume_after[0],
                    cluster=resume_after[1], clock=clock.now())

    for r in range(rounds):
        for c in range(ft.num_clusters):
            if resume_after is not None and (r, c) <= resume_after:
                continue                     # completed before the crash
            members = np.where(assign == c)[0]
            if len(members) == 0:
                continue
            take = min(ft.clients_per_round, len(members))
            sel = rng.choice(members, take, replace=False)
            # systems heterogeneity (paper §1): stragglers miss the round
            # deadline and are excluded from aggregation
            if straggler_prob > 0:
                alive = sel[rng.random(len(sel)) >= straggler_prob]
                if len(alive) == 0:
                    alive = sel[:1]               # quorum of one
            else:
                alive = sel
            alive_set = {int(s) for s in alive}
            for s in sel:
                if int(s) not in alive_set:       # missed the round deadline
                    ledger.record(r, c, int(s), participated=False,
                                  reason="sampled_out")

            t0 = clock.now()
            window_end = (t0 + deadline_s if deadline_s is not None
                          else math.inf)
            participants = [int(s) for s in alive]   # secure mask cohort
            w_alive = np.asarray([weights_all[s] for s in alive], np.float32)
            w_alive = w_alive / w_alive.sum()
            n_started = len(participants)
            round_span = obs.span("fed.round", track=f"fed:cluster{c}",
                                  round=r, cluster=c, clients=n_started,
                                  stragglers=int(take - n_started),
                                  deadline_s=deadline_s, wire=wire)
            round_span.__enter__()

            # -- client fits + wire encode (arrival on the virtual clock) --
            arrivals: List[dict] = []
            for idx, s in enumerate(alive):
                s = int(s)
                will_upload = plan.will_upload(s, r) if plan else True
                measured, ad, l_val = 0.0, None, float("nan")
                fit_t0 = time.perf_counter()
                if will_upload:
                    x, y = client_data[s]
                    batches = _stack_batches(x, y, ft.local_steps,
                                             batch_size,
                                             seed=1000 * r + s)
                    # the fit's time ends at its loss on the host, not at
                    # the dispatch: the straggler rules read it
                    with obs.span("fed.client_fit",
                                  track=f"fed:cluster{c}", client=s,
                                  cluster=c, round=r, steps=ft.local_steps):
                        ad, l = local_update(loss_fn, params,
                                             servers[c].adapters,
                                             batches, steps=ft.local_steps)
                        l_val = float(l)
                    measured = time.perf_counter() - fit_t0
                att = (plan.attempt(s, r, measured) if plan
                       else Attempt(s, r, "ok", measured))
                for k in att.kinds:
                    obs.instant(f"fault.{k}", cat="fault",
                                track=f"fed:cluster{c}", client=s, round=r)
                if att.retries:
                    obs.counter("fed.retries", att.retries)
                if not att.uploads:       # crash-before-upload / hang
                    ledger.record(r, c, s, participated=False,
                                  reason=att.outcome)
                    continue

                delta = _tree_delta(ad, servers[c].adapters)
                ef, payload, new_res = 0.0, None, None
                if secure_int:
                    # shared-grid int8 EF encode + pairwise code masks:
                    # byzantine scale is clipped at the grid edge and
                    # NaN cannot cross an integer wire at all
                    if plan is not None:
                        delta = plan.mutate_delta(s, r, delta)
                    scale_i = n_started * float(w_alive[idx])
                    flat, _ = _flatten_tree(delta)
                    codes, new_res = secure_agg.secure_encode(
                        flat * scale_i, wire_residuals.get(s),
                        step=secure_step)
                    payload = secure_agg.mask_codes(
                        codes, client_id=s, participants=participants,
                        round_idx=r)
                    ef = float(np.linalg.norm(new_res))
                elif secure_aggregation:
                    # float-domain masks over the (optionally quantized)
                    # pre-scaled delta — the legacy secure path
                    scale_i = n_started * float(w_alive[idx])
                    scaled = jax.tree.map(lambda a: a * scale_i, delta)
                    if wire != "f32":
                        scaled, new_res = fedcomm.quantize_update(
                            scaled, wire_residuals.get(s), wire=wire)
                        ef = float(jnp.linalg.norm(new_res))
                    if plan is not None:
                        scaled = plan.mutate_delta(s, r, scaled)
                    payload = secure_agg.mask_update(
                        scaled, client_id=s, participants=participants,
                        round_idx=r)
                else:
                    dq = delta
                    if wire != "f32":
                        # the upload is the adapter DELTA through the
                        # wire: encode (+ carried residual); the server
                        # sees the dequantized view — what the network
                        # actually delivers
                        dq, new_res = fedcomm.quantize_update(
                            delta, wire_residuals.get(s), wire=wire)
                        ef = float(jnp.linalg.norm(new_res))
                    if plan is not None:
                        dq = plan.mutate_delta(s, r, dq)
                    payload = dq
                if ef and obs.enabled():
                    obs.gauge(f"fed.ef_residual_norm.client{s}", ef)
                if wire != "f32" and will_upload:
                    # carried EF residual norm: the quantization error
                    # this client drags into its next round
                    obs.hist("fed.ef_residual_norm", ef)
                arrivals.append({
                    "client": s, "arrival": t0 + att.virtual_s,
                    "virtual_s": att.virtual_s, "fit_t0": fit_t0,
                    "loss": l_val, "weight": float(weights_all[s]),
                    "payload": payload, "new_res": new_res, "ef": ef,
                })

            # -- deadline partition ---------------------------------------
            ontime = [a for a in arrivals if a["arrival"] <= window_end]
            late = [a for a in arrivals if a["arrival"] > window_end]
            for a in late:
                obs.instant("fed.deadline_miss", cat="fault",
                            track=f"fed:cluster{c}", client=a["client"],
                            round=r, arrival=a["arrival"])
                if secure_aggregation:
                    # masks bind to this round's cohort: a late masked
                    # upload is useless alone — it counts as a dropout
                    # and the recovery path below re-cancels its masks
                    ledger.record(r, c, a["client"], participated=False,
                                  reason="deadline")
                else:
                    buffer.add(BufferedDelta(
                        a["client"], c, r, a["arrival"], a["weight"],
                        a["loss"], a["payload"]))
                    obs.counter("fed.buffered", 1)
                    ledger.record(r, c, a["client"], participated=False,
                                  reason="deadline")
            # commit EF residuals for uploads that completed in-window
            # (a late non-secure upload still delivered its encoded
            # payload — its residual carries too; crash/hang never
            # encoded, so their residual is untouched, not lost)
            for a in (arrivals if not secure_aggregation else ontime):
                if a["new_res"] is not None:
                    wire_residuals[a["client"]] = a["new_res"]

            # -- aggregate: partial cohort + drained buffer ---------------
            applied_deltas, applied_w, applied_losses = [], [], []
            n_uploads = n_metered = 0
            if secure_aggregation:
                survivors = [a["client"] for a in ontime]
                dropped = [p for p in participants if p not in survivors]
                n_uploads = len(survivors)
                if dropped and survivors:
                    obs.instant("secureagg.recover", cat="fault", round=r,
                                cluster=c, dropped=len(dropped))
                if survivors:
                    if secure_int:
                        code_sum = secure_agg.unmask_sum(
                            [a["payload"] for a in ontime], survivors,
                            participants=participants, round_idx=r)
                        flat_sum = secure_agg.secure_decode_sum(
                            code_sum, step=secure_step)
                        total = _unflatten_tree(flat_sum, flat_spec)
                    else:
                        total = ontime[0]["payload"]
                        for a in ontime[1:]:
                            total = jax.tree.map(lambda x, y_: x + y_,
                                                 total, a["payload"])
                        if dropped:
                            rec = secure_agg.float_recovery_mask(
                                survivors, dropped, round_idx=r,
                                like=total)
                            total = jax.tree.map(lambda x, m: x - m,
                                                 total, rec)
                    denom = float(sum(
                        n_started * w_alive[participants.index(sv)]
                        for sv in survivors))
                    avg_delta = jax.tree.map(lambda x_: x_ / denom, total)
                    finite = all(bool(jnp.all(jnp.isfinite(l)))
                                 for l in jax.tree.leaves(avg_delta))
                    for a in ontime:
                        ledger.record(
                            r, c, a["client"],
                            participated=finite,
                            wall_s=a["virtual_s"],
                            wire_bytes=client_wire_bytes,
                            ef_norm=a["ef"], t0=a["fit_t0"],
                            **({} if finite
                               else {"reason": "corrupt_aggregate"}))
                    if finite:
                        applied_deltas, applied_w = [avg_delta], [1.0]
                        applied_losses = [a["loss"] for a in ontime]
                        n_metered = len(survivors)
                    else:
                        # only the float-masked wire can carry NaN; the
                        # int8 secure wire rejects this structurally
                        obs.instant("fed.reject", cat="fault", round=r,
                                    cluster=c, reason="corrupt_aggregate")
                        obs.counter("fed.rejected.corrupt_aggregate", 1)
            else:
                drained, stale_rejects = buffer.drain(c, r, window_end)
                for e, staleness in stale_rejects:
                    obs.instant("fed.reject", cat="fault",
                                track=f"fed:cluster{c}", client=e.client,
                                round=r, reason="stale",
                                staleness=staleness)
                    obs.counter("fed.rejected.stale", 1)
                    ledger.record(r, c, e.client, participated=False,
                                  wire_bytes=client_wire_bytes,
                                  reason="stale", staleness_rejected=True)
                # the apply path shares drain's boundary predicate: a
                # drained entry at staleness >= limit never reaches
                # apply_deltas, and the ledgered staleness is the same
                # floored value drain decayed by
                cohort = (
                    [(a["client"], a["payload"], a["weight"], a["loss"],
                      a["virtual_s"], a["fit_t0"], a["ef"], 0)
                     for a in ontime] +
                    [(e.client, e.delta, w, e.loss, 0.0, None, 0.0,
                      buffer.staleness_of(r, e.origin_round))
                     for e, w in drained
                     if not buffer.is_stale(
                         buffer.staleness_of(r, e.origin_round))])
                n_uploads = len(cohort) + len(stale_rejects)
                verdicts = validate_deltas([p for _, p, *_ in cohort],
                                           byz_k=byzantine_norm_k)
                for (cl, payload, w, l_val, virt, ft0, ef,
                     stale), (ok, why, nrm) in zip(cohort, verdicts):
                    if ok:
                        applied_deltas.append(payload)
                        applied_w.append(w)
                        n_metered += 1
                        if math.isfinite(l_val):
                            applied_losses.append(l_val)
                        ledger.record(r, c, cl, participated=True,
                                      wall_s=virt,
                                      wire_bytes=client_wire_bytes,
                                      ef_norm=ef, delta_norm=nrm, t0=ft0,
                                      **({"buffered_staleness": stale}
                                         if stale else {}))
                    else:
                        obs.instant("fed.reject", cat="fault",
                                    track=f"fed:cluster{c}", client=cl,
                                    round=r, reason=why, norm=nrm)
                        obs.counter(f"fed.rejected.{why}", 1)
                        ledger.record(r, c, cl, participated=False,
                                      wall_s=virt,
                                      wire_bytes=client_wire_bytes,
                                      reason=why)

            prev_adapters = (servers[c].adapters
                             if obs.enabled() and applied_deltas else None)
            if applied_deltas:
                with obs.span("fed.aggregate", track=f"fed:cluster{c}",
                              round=r, cluster=c,
                              clients=len(applied_deltas),
                              secure=secure_aggregation):
                    servers[c].apply_deltas(applied_deltas,
                                            np.asarray(applied_w,
                                                       np.float32))
            else:
                obs.instant("fed.round_empty", cat="fault", round=r,
                            cluster=c, uploads=n_uploads)
                obs.flight_maybe_dump(f"fed.round{r}.cluster{c}.empty")
            if applied_deltas and len(applied_deltas) * 2 < n_started:
                # distress: most of the cohort was lost this window
                obs.flight_maybe_dump(f"fed.round{r}.cluster{c}.partial")

            # comm is metered over the uploads whose bytes were actually
            # AGGREGATED this window — crashed/hung clients moved no
            # bytes, rejected uploads keep their per-record bytes for
            # audit but stay out of the "one number" sums, and a late
            # upload is priced in the window that applies it — so the
            # ledger's participated per-cluster sums equal Σ bytes_up
            # exactly, faults or not
            stats = comm.fedtime_round(
                params, clients_per_round=n_metered,
                num_clusters=ft.num_clusters, wire=wire)
            loss_r = (float(np.mean(applied_losses))
                      if applied_losses else float("nan"))
            if applied_deltas:
                logs.append(RoundLog(r, c, loss_r, stats))
            if obs.enabled() and prev_adapters is not None:
                # round-over-round adapter movement: ||agg_t - agg_{t-1}||
                # per cluster — flat-lining under a quantized wire with no
                # EF state is the classic correlated-bias symptom
                dn = float(jnp.sqrt(sum(
                    jnp.sum((a.astype(jnp.float32) -
                             b.astype(jnp.float32)) ** 2)
                    for a, b in zip(jax.tree.leaves(servers[c].adapters),
                                    jax.tree.leaves(prev_adapters)))))
                obs.gauge(f"fed.adapter_delta_norm.cluster{c}", dn)
                obs.hist("fed.adapter_delta_norm", dn)
                obs.gauge(f"fed.round_loss.cluster{c}", loss_r)
                obs.counter("fed.wire_bytes",
                            stats.bytes_up + stats.bytes_down)
                obs.counter_track(f"fed.cluster{c}", delta_norm=dn,
                                  loss=loss_r)
            # the deadline bounds the window even when stragglers ran
            # long; without one the slowest upload sets the pace
            finite_arrivals = [a["arrival"] for a in arrivals
                               if math.isfinite(a["arrival"])]
            clock.advance_to(window_end if deadline_s is not None
                             else max(finite_arrivals, default=t0))
            round_span.__exit__(None, None, None)
            if snapshot_path:
                _write_snapshot(snapshot_path, r=r, c=c, rounds=rounds,
                                clock=clock, rng=rng, servers=servers,
                                wire_residuals=wire_residuals,
                                ledger=ledger, logs=logs, buffer=buffer)
            if progress:
                progress(f"round {r} cluster {c}: "
                         f"loss={loss_r:.4f} "
                         f"comm={stats.megabytes:.2f}MB")
        if obs.enabled():
            # device-memory watermark at the round boundary (devmem track)
            obs.watermark(f"fed.round{r}")

    ledger.to_trace()
    fleet_out = fleet_out or os.environ.get("REPRO_FLEET_OUT")
    if fleet_out:
        ledger.dump(fleet_out)
    return FedResult([s.adapters for s in servers], params, logs,
                     assign, frac, fleet=ledger)


# ---------------------------------------------------------------------------
# Two-phase pipeline with DPO alignment (paper Fig. 1a)
# ---------------------------------------------------------------------------

def two_phase_fit(cfg: ModelConfig, client_data, *, rounds_sft: int = 2,
                  rounds_forecast: int = 3, dpo_steps: int = 20,
                  batch_size: int = 16, key=None, progress=None):
    """SFT (instance norm) -> DPO alignment -> forecasting FT (RevIN)."""
    key = key if key is not None else jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)

    # Phase 1: supervised fine-tuning
    res_sft = federated_fit(cfg, client_data, rounds=rounds_sft,
                            batch_size=batch_size, key=k1, phase="sft",
                            progress=progress)

    # Global consolidation: average cluster adapters for the DPO stage
    global_ad = fedavg(res_sft.adapters_per_cluster,
                       jnp.ones(len(res_sft.adapters_per_cluster)))
    params = merge_lora(res_sft.base_params, global_ad)

    # Phase 1.5: DPO alignment (server-side, synthetic preference pairs)
    ref_params = params
    x_all = np.concatenate([x[:8] for x, _ in client_data])[:batch_size]
    y_all = np.concatenate([y[:8] for _, y in client_data])[:batch_size]
    pairs = dpo.make_preference_pairs(k2, jnp.asarray(x_all),
                                      jnp.asarray(y_all))

    def dpo_loss_fn(p, batch):
        return dpo.dpo_loss(p, ref_params, cfg, batch,
                            beta=cfg.fedtime.dpo_beta)

    pairs_stacked = jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (dpo_steps,) + a.shape), pairs)
    aligned_ad, dpo_l = local_update(dpo_loss_fn, params, global_ad,
                                     pairs_stacked, steps=dpo_steps,
                                     lr=1e-4)
    if progress:
        progress(f"DPO alignment loss={float(dpo_l):.4f}")
    params = merge_lora(params, aligned_ad)

    # Phase 2: forecasting fine-tuning (RevIN), warm-started with the
    # SFT+DPO adapters (paper: "transfer the updated weights of the
    # backbone model to the forecasting fine-tuning phase")
    res = federated_fit(cfg, client_data, rounds=rounds_forecast,
                        batch_size=batch_size, key=k3, phase="forecast",
                        base_params=res_sft.base_params,
                        init_adapters=lora_tree(params), progress=progress)
    res.logs = res_sft.logs + res.logs
    return res
