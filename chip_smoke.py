"""Smoke run of the main path on the chip, in one process, at the published
widths of qwen3-0.6b (28 layers, d_model 1024, 16/8 heads, head_dim 128,
d_ff 3072, vocab 151,936) with random weights made from a seed.

  python chip_smoke.py              # one chip: federated round + serving
  python chip_smoke.py --chips 4    # four chips: the mesh path only

One chip runs two phases:

  a. federated round — ``train.fed_trainer.federated_fit`` on the FedTime
     front end (RevIN, channel split, patching) over the NF4-quantized
     backbone with LoRA adapters, clients uploading on the int8
     error-feedback wire; then a forecast with ``core.fedtime.forward``.
  b. serving — ``launch.serve.run_engine`` (the function behind
     ``python -m repro.launch.serve --full-config --engine``) on a paged
     KV pool with prefix sharing, a cache long enough that decode takes
     the Pallas flash-decode kernel, and two requests with the same prompt
     so a copy-on-write block copy runs; the kernel is checked against the
     float32 reference at the same shapes.

``--chips 4`` runs only what exists across chips: the LoRA ring
aggregation on a ``data=4`` mesh against the psum aggregation, and the
federated train step on a ``(data=2, model=2)`` mesh against the same
global batch on one chip.

Every figure printed is a smoke-run figure (compile included where said),
not a benchmark number.  The script exits non-zero without its final line
when the first device is not a TPU or when any phase fails; the final line
is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen3-0.6b"
BF16_TOL = 2e-2
KERNEL_MARK = "tpu_custom_call"       # a compiled Pallas kernel in HLO text


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# a. federated round
# ---------------------------------------------------------------------------

def federated_phase(cfg, *, clients=8, clusters=2, local_steps=4, rounds=2,
                    batch=4, timesteps=4000, seed=0):
    """Rounds of ``federated_fit`` at the model's widths with only the
    federation's sizes cut; returns the fit result."""
    import jax
    import numpy as np

    from repro.core import fedtime
    from repro.data.federated import client_windows, partition_clients
    from repro.data.timeseries import DATASETS, generate, train_test_split
    from repro.train.fed_trainer import federated_fit

    ft = dataclasses.replace(cfg.fedtime, num_clients=clients,
                             num_clusters=clusters,
                             clients_per_round=clients // clusters,
                             local_steps=local_steps)
    cfg = cfg.replace(fedtime=ft)
    train, _ = train_test_split(generate(DATASETS["etth1"],
                                         timesteps=timesteps, seed=seed))
    cdata = client_windows(
        partition_clients(train, clients, seed=seed, channels_per_client=2),
        ft.lookback, ft.horizon, max_windows=32, seed=seed)

    round_end = {}

    def progress(msg):                  # "round {r} cluster {c}: ..."
        round_end[int(msg.split()[1])] = time.perf_counter()
        say(f"fed {msg}")

    t0 = time.perf_counter()
    res = federated_fit(cfg, cdata, rounds=rounds, batch_size=batch,
                        key=jax.random.PRNGKey(seed), wire="int8",
                        progress=progress)
    losses = [l.train_loss for l in res.logs]
    if not losses or not all(np.isfinite(losses)):
        raise RuntimeError(f"non-finite federated losses: {losses}")
    for r in range(rounds):
        got = [l for l in res.logs if l.round == r]
        if not got:
            raise RuntimeError(f"round {r} aggregated no cluster")
        say(f"fed round {r}: loss "
            + ", ".join(f"cluster{l.cluster}={l.train_loss:.6f}"
                        for l in got)
            + f"; round time {round_end[r] - round_end.get(r - 1, t0):.3f}s"
            + (" (includes compile)" if r == 0 else "")
            + " [smoke-run figure]")

    x = jax.numpy.asarray(cdata[0][0][:batch])
    fwd = jax.jit(lambda p, x: fedtime.forward(p, cfg, x, remat=False))
    y = np.asarray(fwd(res.params_for_cluster(0), x))
    want = (batch, ft.horizon, x.shape[-1])
    if y.shape != want or not np.all(np.isfinite(y)):
        raise RuntimeError(f"forecast shape {y.shape} (want {want}) or "
                           f"non-finite values")
    say(f"fed forecast cluster 0: shape {y.shape}, finite")
    return res


# ---------------------------------------------------------------------------
# b. serving
# ---------------------------------------------------------------------------

def serving_trace(cfg, *, n_req=8, prompt_len=40, gen=16, seed=0):
    """``n_req`` greedy requests of one prompt length (one prefill
    signature).  req1 repeats req0's prompt — a whole-prompt hit that
    shares req0's partly filled last block, so the first decode write
    copies it (CoW); req2 shares req0's block-aligned prefix and diverges
    in its tail."""
    import numpy as np
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, prompt_len).tolist()
               for _ in range(n_req)]
    prompts[1] = list(prompts[0])
    prompts[2] = prompts[0][:prompt_len // 2] + prompts[2][prompt_len // 2:]
    return [{"id": f"req{i}", "prompt": p, "max_new_tokens": gen,
             "arrival_step": i // 2} for i, p in enumerate(prompts)]


def solo_greedy(cfg, params, prompt, gen, cache_len, fns):
    """Reference: the request alone through prefill + the fixed-batch
    serve step (contiguous ring cache)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.steps import make_serve_step
    from repro.models.registry import get_model
    if not fns:
        api = get_model(cfg)
        fns["prefill"] = jax.jit(lambda p, t: api.prefill(
            p, cfg, {"tokens": t}, cache_len=cache_len))
        fns["step"] = jax.jit(make_serve_step(cfg), donate_argnums=(1,))
    cache, logits = fns["prefill"](params, jnp.asarray([prompt], jnp.int32))
    tok = jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32)[:, None]
    out = [tok]
    for i in range(gen - 1):
        tok, cache = fns["step"](params, cache, {
            "token": tok, "pos": jnp.asarray([len(prompt) + i], jnp.int32)})
        out.append(tok)
    return [int(t[0, 0]) for t in np.asarray(out)]


def step_hlo(engine):
    """Compiled text of the engine's serve step at its run's shapes."""
    return engine._step_fn.lower(engine.params, engine.pool.cache,
                                 engine.decode_batch()).compile().as_text()


def check_decode_kernel(cfg, *, batch, cache_len, block_size, seed=0):
    """``ops.flash_decode`` vs ``ref.flash_decode_ref`` (float32) on a bf16
    cache at the serving shapes, ring and paged layouts; returns the
    largest absolute error."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref
    Hk, H, D = cfg.num_kv_heads, cfg.num_heads, cfg.resolved_head_dim()
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (batch, 1, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (batch, cache_len, Hk, D), jnp.float32)
    v = jax.random.normal(ks[2], (batch, cache_len, Hk, D), jnp.float32)
    q_pos = jnp.asarray(np.linspace(cache_len // 4, cache_len - 1, batch),
                        jnp.int32)
    kv_pos = jnp.broadcast_to(jnp.arange(cache_len, dtype=jnp.int32),
                              (batch, cache_len))
    # paged: each lane's logical blocks scattered over a shuffled pool
    T = cache_len // block_size
    perm = jax.random.permutation(ks[3], batch * T).reshape(batch, T)
    nb = batch * T

    def to_pool(x):
        blocks = x.reshape((batch * T, block_size) + x.shape[2:])
        return jnp.zeros_like(blocks).at[perm.reshape(-1)].set(blocks)

    bf = lambda x: x.astype(jnp.bfloat16)  # noqa: E731
    worst = 0.0
    cases = {
        "ring": ((bf(q), bf(k), bf(v), kv_pos, q_pos), {}),
        "paged": ((bf(q), to_pool(bf(k)), to_pool(bf(v)), to_pool(kv_pos),
                   q_pos), {"block_tables": perm}),
    }
    for name, (args, kw) in cases.items():
        fn = jax.jit(lambda *a, kw=kw: ops.flash_decode(*a, **kw))
        if KERNEL_MARK not in fn.lower(*args).compile().as_text():
            raise RuntimeError(f"{name} decode did not take the kernel")
        got = np.asarray(fn(*args), np.float32)
        f32 = [a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a
               for a in args]
        want = np.asarray(ref.flash_decode_ref(*f32, **kw), np.float32)
        err = float(np.abs(got - want).max())
        if not np.allclose(got, want, rtol=BF16_TOL, atol=BF16_TOL):
            raise RuntimeError(f"{name} flash_decode vs reference: max abs "
                               f"err {err}")
        say(f"kernel {name} flash_decode vs f32 reference: max abs err "
            f"{err:.3e} (B={batch}, S={cache_len}, pool {nb}x{block_size})")
        worst = max(worst, err)
    return worst


def serving_phase(cfg, params, *, slots=4, cache_len=2048, n_req=8,
                  prompt_len=40, gen=16, block_size=16, seed=0):
    """The continuous-batching engine on a paged pool with prefix sharing;
    returns (summary, greedy token mismatches vs solo decode)."""
    import numpy as np

    from repro.kernels import ops
    from repro.launch.serve import run_engine
    say(f"decode path at {cache_len} slots: {ops.decode_mode(cache_len)}")
    trace = serving_trace(cfg, n_req=n_req, prompt_len=prompt_len, gen=gen,
                          seed=seed)
    t0 = time.perf_counter()
    done, summ, engine = run_engine(
        cfg, params, trace, slots=slots, cache_len=cache_len, paged=True,
        block_size=block_size, share_prefixes=True)
    say(f"serving run {time.perf_counter() - t0:.3f}s (includes compile), "
        f"steady decode {summ['steady_tok_per_s']:.1f} tok/s "
        f"[smoke-run figures]")
    if len(done) != n_req or any(len(f.tokens) != gen
                                 for f in done.values()):
        raise RuntimeError(f"engine finished {len(done)}/{n_req} requests")
    if summ["cow_copies"] < 1:
        raise RuntimeError("no copy-on-write block copy ran")
    if engine.num_step_signatures() != 1:
        raise RuntimeError(f"{engine.num_step_signatures()} serve_step "
                           f"signatures")
    if KERNEL_MARK not in step_hlo(engine):
        raise RuntimeError("compiled serve_step holds no Pallas kernel")
    say(f"serve_step compiled with tpu_custom_call; "
        f"{summ['cow_copies']} CoW block copies through "
        f"{'paged_block_copy' if ops.use_kernels() else 'the XLA copy'}")
    check_decode_kernel(cfg, batch=slots, cache_len=cache_len,
                        block_size=engine.pool.block_size, seed=seed)

    fns: dict = {}
    mismatches = 0
    for r in trace:
        solo = solo_greedy(cfg, params, r["prompt"], gen, cache_len, fns)
        mismatches += int(np.sum(np.asarray(solo) !=
                                 np.asarray(done[r["id"]].tokens)))
    print(f"engine-vs-solo greedy token mismatches: {mismatches} of "
          f"{n_req * gen}", flush=True)
    return summ, mismatches


# ---------------------------------------------------------------------------
# --chips 4: the mesh path
# ---------------------------------------------------------------------------

def ring_phase(cfg, mesh, *, members=8, seed=0):
    """``fedcomm.ring_aggregate`` vs the psum aggregation of
    ``fed.aggregate_adapters`` on the model's real LoRA payload: the f32
    wire exactly (integer payload), the int8 wire within the ring tests'
    tolerance."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import fedtime
    from repro.core.lora import FAMILY_TARGETS, attach_lora, lora_tree
    from repro.dist import fed, fedcomm

    ft = cfg.fedtime
    shapes = jax.eval_shape(lambda k: lora_tree(attach_lora(
        fedtime.init(cfg, k), k, rank=ft.lora_rank, alpha=ft.lora_alpha,
        targets=FAMILY_TARGETS["dense"])), jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    stack = jax.tree.map(lambda s: jnp.asarray(rng.integers(
        -8, 9, (members,) + s.shape).astype(np.float32)), shapes)
    n_elems = sum(l.size // members for l in jax.tree.leaves(stack))
    ones = jnp.ones((members,), jnp.float32)
    wf = rng.random(members).astype(np.float32)
    wf = jnp.asarray(wf / wf.sum())

    def psum(w):
        os.environ["REPRO_FED_RING"] = "0"
        try:
            return fed.aggregate_adapters(stack, w, mesh)
        finally:
            del os.environ["REPRO_FED_RING"]

    with mesh:
        ring32, ps32 = fedcomm.ring_aggregate(stack, ones, mesh,
                                              wire="f32"), psum(ones)
        ring8, ps_w = fedcomm.ring_aggregate(stack, wf, mesh,
                                             wire="int8"), psum(wf)
    exact = all(np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(jax.tree.leaves(ring32),
                                jax.tree.leaves(ps32)))
    err8 = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
               for a, b in zip(jax.tree.leaves(ring8), jax.tree.leaves(ps_w)))
    say(f"ring aggregate on data={mesh.shape['data']}: payload {n_elems} "
        f"f32 elems/member x {members} members; f32 ring == psum exactly: "
        f"{exact}; int8 ring vs psum max abs err {err8:.4f}")
    if not exact or err8 > 0.3:
        raise RuntimeError("ring aggregation disagrees with psum")


def mesh_train_phase(cfg, mesh, *, batch=8, seq=256, steps=2, seed=0):
    """The federated train step with parameters and batch placed by
    ``dist.sharding`` on ``mesh``, against the same global batches on one
    chip; returns (mesh losses, one-chip losses)."""
    import jax
    import numpy as np

    from repro.core.lora import FAMILY_TARGETS, attach_lora, lora_tree
    from repro.data.tokens import lm_batches, markov_tokens
    from repro.launch.steps import make_fed_train_step
    from repro.launch.train import place_batch, place_state, synth_batch
    from repro.models.registry import get_model
    from repro.optim.adamw import adamw_init

    api = get_model(cfg)
    params = attach_lora(api.init(cfg, jax.random.PRNGKey(seed)),
                         jax.random.PRNGKey(seed + 1), rank=4, alpha=8.0,
                         targets=FAMILY_TARGETS[cfg.family])
    opt = adamw_init(lora_tree(params))
    it = lm_batches(markov_tokens(50_000, cfg.vocab_size, seed=seed), batch,
                    seq + 1, seed=seed)
    batches = [synth_batch(cfg, batch, seq, it) for _ in range(steps)]
    step_fn = make_fed_train_step(cfg)

    one = jax.jit(step_fn)
    p1, o1, ref_losses = params, opt, []
    for i, b in enumerate(batches):
        p1, o1, l = one(p1, o1, b, np.int32(i))
        ref_losses.append(float(l))
    del p1, o1

    mp, mo, (psh, osh) = place_state(params, opt, mesh, fed=True)
    total = sum(x.nbytes for x in jax.tree.leaves(mp))
    per_dev = {}
    for x in jax.tree.leaves(mp):
        for sh in x.addressable_shards:
            per_dev[sh.device] = per_dev.get(sh.device, 0) + sh.data.nbytes
    if max(per_dev.values()) >= total:
        raise RuntimeError("a device holds the whole parameter tree")
    say(f"params on mesh {dict(mesh.shape)}: {total} B in all, "
        f"{min(per_dev.values())}-{max(per_dev.values())} B per device")
    step = jax.jit(step_fn, donate_argnums=(0, 1),
                   out_shardings=(psh, osh, None))
    losses = []
    with mesh:
        for i, b in enumerate(batches):
            mp, mo, l = step(mp, mo, place_batch(b, mesh), np.int32(i))
            losses.append(float(l))
    say(f"fed train step losses on mesh {losses}, one chip {ref_losses}")
    if not np.allclose(losses, ref_losses, rtol=BF16_TOL, atol=BF16_TOL):
        raise RuntimeError("mesh loss disagrees with the one-chip loss")
    return losses, ref_losses


# ---------------------------------------------------------------------------

_COMPILE_S = [0.0]          # backend compile (or compile-cache load) seconds


def _count_compile(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[0] += duration


def run_phase(name, fn, *args, **kw):
    """Run one phase; print its wall seconds and how many of them JAX spent
    compiling (or loading compiled code from the cache)."""
    c0, t0 = _COMPILE_S[0], time.perf_counter()
    out = fn(*args, **kw)
    say(f"{name} phase {time.perf_counter() - t0:.3f}s wall, of which "
        f"{_COMPILE_S[0] - c0:.3f}s compiling [smoke-run figures]")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the mesh phase (ring aggregation + "
                         "sharded federated step)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (first device: {devices[0].platform}); "
              f"this script measures nothing elsewhere", file=sys.stderr)
        return 1
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"device(s)", file=sys.stderr)
        return 1
    jax.monitoring.register_event_duration_secs_listener(_count_compile)

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh
    from repro.models.registry import get_model
    say(f"device {devices[0].device_kind} x {len(devices)}, jax "
        f"{jax.__version__}, compile cache {enable_compile_cache()}")
    cfg = get_config(ARCH)

    if args.chips == 4:
        run_phase("ring", ring_phase, cfg, make_host_mesh(model=1),
                  seed=args.seed)
        run_phase("mesh train", mesh_train_phase, cfg,
                  make_host_mesh(model=2), seed=args.seed)
    else:
        run_phase("federated", federated_phase, cfg, seed=args.seed)
        run_phase("serving", lambda: serving_phase(
            cfg, get_model(cfg).init(cfg, jax.random.PRNGKey(args.seed)),
            seed=args.seed))

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
