"""Driver: FOLB rounds of a language model on one chip's share of it,
through the program's large-model round path: ``launch.steps.
build_train_step`` -> ``fed.distributed.folb_round`` with
``agg_backend="flat"`` and ``algo="folb"`` (flat (K, D) buffers and the
FOLB Pallas kernel).

The configuration file is the model's published ``config.json`` cut to the
chip's share (``reduced``, ``deployment``); the program's configuration
module of the same name is given its numbers.  The weights are drawn from
the seed by the plain reference (``init_params`` of ``bench/configs/
<config>.py``, numpy, in its own layout) and mapped into the program's
tree as float32 master weights (``master_dtype``), computed in
``param_dtype``.

Traffic: ``n_clients`` client shards, each ``seqs_per_client`` sequences of
``seq_len`` + 1 ids drawn from the client's own Zipf(``zipf``) over its own
permutation of the vocabulary slice, all made from the seed in set-up.  A
round draws ``clients_per_round`` clients uniformly; each takes
``local_steps`` prox-SGD steps (``lr``, ``mu``) on its whole shard.  A unit
of work is one round, timed until the new params and the round's metrics
are on the host (closed loop, one caller).  Round 0 warms up in set-up on a
copy of the seeded weights that is thrown away; the window runs rounds 1,
2, ... from the seeded weights.

After the window, round 1 is run again from the seeded weights and
compared with the plain reference (``bench/reference/lm_round.py`` over
``bench/configs/<config>.py``) from the same weights, and with the
window's own round 1.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

from bench.core import gen, lm_counts, refs
from bench.reference import lm_round as ref_round

ROUND_PROGRAMS = ("jit_train_round",)


def arch_config(cfg: dict):
    """The program's configuration of ``cfg["name"]``, given the file's
    numbers (a test may hand a smaller file of the same model)."""
    from repro.configs import get_config
    from repro.configs.base import AttnKind, RopeConfig
    base = get_config(cfg["name"])

    def kind(name):
        r = cfg["rope_parameters"][name]
        rope = RopeConfig(theta=float(r["rope_theta"]))
        if r.get("rope_type", "default") == "yarn":
            rope = RopeConfig(
                theta=float(r["rope_theta"]), yarn_factor=float(r["factor"]),
                original_max_position=int(r["original_max_position_embeddings"]),
                beta_fast=float(r["beta_fast"]), beta_slow=float(r["beta_slow"]),
                attention_factor=float(r["attention_factor"]))
        window = cfg["sliding_window"] if name == "sliding_attention" else 0
        return AttnKind("window" if window else "full", window, rope)

    return dataclasses.replace(
        base, n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        vocab=cfg["vocab_size"], tie_embeddings=cfg["tie_word_embeddings"],
        attn_period=tuple(kind(t) for t in cfg["layer_types"]),
        moe=dataclasses.replace(
            base.moe, n_experts=cfg["num_experts_router"],
            top_k=cfg["num_experts_per_tok"],
            expert_d_ff=cfg["moe_intermediate_size"],
            n_held=cfg["num_experts"],
            held_offset=cfg["experts_held_offset"]),
        param_dtype=cfg["param_dtype"])


def client_shards(seed: int, cfg: dict, tr: dict) -> np.ndarray:
    """(n_clients, seqs_per_client, seq_len + 1) int32 ids: client c draws
    ranks from Zipf(``zipf``) over the slice and maps them through its own
    permutation of it."""
    V = cfg["vocab_size"]
    n, m, L = tr["n_clients"], tr["seqs_per_client"], tr["seq_len"] + 1
    cdf = np.cumsum(np.arange(1, V + 1, dtype=np.float64) ** -tr["zipf"])
    cdf /= cdf[-1]
    out = np.empty((n, m, L), np.int32)
    for c in range(n):
        rng = np.random.default_rng(gen.seed_ints(seed, 10, 2) + [c])
        perm = rng.permutation(V).astype(np.int32)
        ranks = np.searchsorted(cdf, rng.random((m, L)), side="right")
        out[c] = perm[np.minimum(ranks, V - 1)]
    return out


def round_clients(seed: int, r: int, tr: dict) -> np.ndarray:
    """The clients of round ``r``: uniform, with replacement."""
    rng = np.random.default_rng(gen.seed_ints(seed, 11, 2) + [r])
    return rng.integers(0, tr["n_clients"], tr["clients_per_round"])


class Session:
    CHECK_ROUND = 1

    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.config
        self.tr = ctx.traffic
        self.records = []        # (round, host metrics) of the window
        self.tracing = False
        self._ref = None

    # ------------------------------------------------------------ set-up
    def setup_inputs(self):
        import jax
        from repro.fed.distributed import RoundConfig
        tr = self.tr
        self.arch = arch_config(self.cfg)
        self.rc = RoundConfig(
            algo="folb", n_clients=tr["clients_per_round"],
            local_steps=tr["local_steps"], lr=tr["lr"], mu=tr["mu"],
            remat=tr["remat"], agg_backend="flat", agg_dtype=tr["agg_dtype"])
        self.mesh = jax.make_mesh((1, 1), ("data", "model"),
                                  devices=jax.devices()[:1],
                                  axis_types=(jax.sharding.AxisType.Auto,) * 2)
        self.shards = client_shards(self.ctx.seed, self.cfg, tr)
        self.model = refs.config_module(self.ctx)
        self.init = self.model.init_params(
            self.cfg, gen.seed_ints(self.ctx.seed, 12, 2))
        dt = np.dtype(self.cfg["master_dtype"])
        self.init_program = jax.tree.map(
            lambda x: x.astype(dt, copy=False),
            program_layout(self.arch, self.init))

    def setup(self):
        import jax
        from repro.launch import steps
        t0 = time.perf_counter()
        self.setup_inputs()
        t1 = time.perf_counter()
        self.step, _ = steps.build_train_step(self.arch, self.mesh, self.rc,
                                              "train_8k")
        # warm-up on a copy of the seeded weights, then start from them
        jax.block_until_ready(self._round(self.initial_params(), 0))
        self.params = self.initial_params()
        jax.block_until_ready(self.params)
        self.n = 1
        self.setup_phases = {"inputs_s": t1 - t0,
                             "warm_s": time.perf_counter() - t1}

    def initial_params(self):
        """The seeded weights in the program's layout, as master weights
        placed for the round."""
        import jax
        from repro.launch import steps
        _, shard = steps.param_shardings(self.arch, self.mesh)
        return jax.device_put(self.init_program, shard)

    def batch(self, r: int, rows=None) -> dict:
        """Host arrays of round ``r``: tokens and next-token labels (K, b,
        seq_len), b the first ``rows`` of each client's sequences (all of
        them by default)."""
        seqs = self.shards[round_clients(self.ctx.seed, r, self.tr)][:, :rows]
        return {"tokens": seqs[..., :-1], "labels": seqs[..., 1:]}

    def _round(self, params, r: int, step=None):
        """The program's round ``r`` from ``params`` (donated): (new params,
        device metrics)."""
        from repro.telemetry import profiler as prof
        b = {k: prof.to_device(v) for k, v in self.batch(r).items()}
        return (step or self.step)(params, b)

    # ------------------------------------------------------------ window
    def trace_on(self):
        from repro.telemetry import profiler as prof
        prof.reset()
        prof.enable()
        self.tracing = True

    def unit(self):
        import jax
        from repro.telemetry import profiler as prof
        r = self.n
        self.params, metrics = self._round(self.params, r)
        jax.block_until_ready(self.params)
        with prof.span("round/moe_load"):
            host = {k: prof.fetch(v) for k, v in metrics.items()}
            load = host["moe_load"]
            prof.count("moe_tokens_held", int(load.sum()))
            prof.count("moe_tokens_max_expert", int(load.max(axis=1).sum()))
        self.n += 1
        self.records.append((r, host))
        return r, host

    def work(self, records) -> dict:
        return round_work(self.cfg, self.tr, records)

    def finish(self):
        if self.tracing:
            from repro.telemetry import profiler as prof
            prof.disable()
            self.tracing = False

    # ------------------------------------------------------------ check
    def program_round(self, fault=None):
        """Round 1 of the program from the seeded weights: (new params on
        the host in the reference's layout, host metrics).  With ``fault``
        (a ``faults`` entry) that round runs the faulty program."""
        import jax
        from repro.launch import steps
        self.params = None
        arch, planted = fault or (self.arch, contextlib.nullcontext())
        with planted:
            step = self.step if fault is None else steps.build_train_step(
                arch, self.mesh, self.rc, "train_8k")[0]
            after, metrics = self._round(self.initial_params(),
                                         self.CHECK_ROUND, step)
            metrics = jax.device_get(metrics)
        return jax.device_get(ref_layout(self.arch, after)), metrics

    def check(self, fault=None) -> dict:
        """Round 1 against the reference: the worst relative gap of the
        client losses and of the FOLB scores, the worst leaf's distance of
        the new params over the reference's change, the held assignments
        left uncomputed, and whether the rerun gave the window's numbers
        (``replay_mismatch``, when the window ran round 1)."""
        after, got = self.program_round(fault)
        out = compare(got, after, self.init, self.reference())
        window = dict(self.records).get(self.CHECK_ROUND)
        if window is not None and fault is None:
            out["replay_mismatch"] = float(not all(
                np.array_equal(window[k], got[k]) for k in got))
        return out

    def reference(self, dtype: str = "float32", rows=None):
        """The reference's round 1 from the seeded weights, its params on
        the host; ``rows`` as in ``batch``.  The sound one (float32, whole
        shards) is kept for the session."""
        import jax
        sound = dtype == "float32" and rows is None
        if sound and self._ref is not None:
            return self._ref
        b = self.batch(self.CHECK_ROUND, rows)
        res = ref_round.run(
            self.model, self.cfg, jax.device_put(self.init), b["tokens"],
            b["labels"], self.tr["lr"], self.tr["mu"],
            self.tr["local_steps"], dtype=dtype,
            storage=self.tr["agg_dtype"])
        res.params = jax.device_get(res.params)
        if sound:
            self._ref = res
        return res

    # ------------------------------------------------------ calibration
    def calibration_program(self) -> dict:
        """The comparison of round 1, without a window."""
        return self.check()

    def calibration_upper(self):
        """(kind, numbers) with, in the program's place: the reference in
        float8 (e4m3, the precision below the configuration's bfloat16;
        the control); the reference with each client trained on half of
        its sequences (``half_batch``); and the program with each fault of
        ``faults`` planted."""
        import jax
        self.setup_inputs()
        ref = self.reference()
        for kind, res in (
                ("control", lambda: self.reference("float8_e4m3fn")),
                ("half_batch", lambda: self.reference(
                    rows=self.tr["seqs_per_client"] // 2))):
            r = res()
            yield kind, compare({"client_losses": r.losses,
                                 "scores": r.scores,
                                 "moe_dropped": np.zeros(1)},
                                r.params, self.init, ref)
            del r
        tokens = self.tr["seqs_per_client"] * self.tr["seq_len"]
        for kind, fault in faults(self.arch, tokens).items():
            yield kind, self.check(fault)
            jax.clear_caches()


def round_work(cfg: dict, tr: dict, records) -> dict:
    """Rounds, model FLOPs, the kernels' executed FLOPs and the FOLB
    kernel's bytes of the given rounds, from the shapes and each round's
    routed rows (``bench.core.lm_counts``)."""
    tot = {"rounds": len(records), "flops": 0.0, "expert_kernel_flops": 0.0,
           "attn_window_flops": 0.0, "attn_full_flops": 0.0,
           "agg_bytes": 0.0}
    for _, host in records:
        c = lm_counts.round_counts(cfg, tr, float(host["moe_load"].sum()))
        tot["flops"] += c["model_flops"]
        tot["expert_kernel_flops"] += c["expert_kernel_flops"]
        tot["attn_window_flops"] += c["attn_kernel_flops"]["sliding_attention"]
        tot["attn_full_flops"] += c["attn_kernel_flops"]["full_attention"]
        tot["agg_bytes"] += c["agg_bytes"]
    return tot


def ref_layout(arch, p) -> dict:
    """The program's params ``p`` of configuration ``arch`` in the
    reference's layout (float32, one dict per layer in depth order)."""
    import jax
    import jax.numpy as jnp
    f32 = lambda x: x.astype(jnp.float32)
    layers = []
    for g in range(arch.n_super_groups()):
        for (_, n), run in zip(arch.attn_runs(), p["period"]):
            for i in range(n):
                lp = jax.tree.map(lambda x: f32(x[g, i]), run)
                attn, moe = lp["attn"], lp["moe"]
                layers.append({
                    "wq": attn["wq"]["w"], "wk": attn["wk"]["w"],
                    "wv": attn["wv"]["w"], "wo": attn["wo"]["w"],
                    "attn_norm": lp["attn_norm"]["scale"],
                    "moe_norm": lp["moe_norm"]["scale"],
                    "router": moe["router"]["w"], "w_gate": moe["w_gate"],
                    "w_up": moe["w_up"], "w_down": moe["w_down"]})
    return {"layers": layers, "embed": f32(p["embed"]["w"]),
            "lm_head": f32(p["lm_head"]["w"]),
            "final_norm": f32(p["final_norm"]["scale"])}


def program_layout(arch, ref: dict) -> dict:
    """Host params ``ref`` in the reference's layout as the program's tree
    of configuration ``arch``: the inverse of ``ref_layout``."""
    period, per = [], len(arch.attn_period)
    first = 0
    for _, n in arch.attn_runs():
        def stack(get, first=first, n=n):
            return np.stack([np.stack([get(ref["layers"][g * per + first + i])
                                       for i in range(n)])
                             for g in range(arch.n_super_groups())])
        period.append({
            "attn": {k: {"w": stack(lambda lp, k=k: lp[k])}
                     for k in ("wq", "wk", "wv", "wo")},
            "attn_norm": {"scale": stack(lambda lp: lp["attn_norm"])},
            "moe_norm": {"scale": stack(lambda lp: lp["moe_norm"])},
            "moe": {"router": {"w": stack(lambda lp: lp["router"])},
                    **{k: stack(lambda lp, k=k: lp[k])
                       for k in ("w_gate", "w_up", "w_down")}}})
        first += n
    return {"embed": {"w": ref["embed"]}, "lm_head": {"w": ref["lm_head"]},
            "final_norm": {"scale": ref["final_norm"]},
            "period": tuple(period)}


@contextlib.contextmanager
def capacity_routing(tokens: int, top_k: int, n_experts: int,
                     factor: float = 1.25):
    """While open, the program's held MoE layer computes only the first
    ``factor`` x ``tokens`` x ``top_k`` / ``n_experts`` rows of each held
    expert, in token order (capacity routing, as a dropping layer would);
    the rest of its rows give 0.  Planted by wrapping the grouped FFN entry
    that the layer looks up when it is traced."""
    import jax.numpy as jnp
    from repro.kernels import ops
    cap = int(tokens * top_k * factor / n_experts)
    grouped = ops.moe_grouped_ffn

    def kept(xs, w_gate, w_up, w_down, sizes):
        ends = jnp.cumsum(sizes)
        row = jnp.arange(xs.shape[0])
        g = jnp.minimum(jnp.searchsorted(ends, row, side="right"),
                        sizes.shape[0] - 1)
        keep = row - (ends[g] - sizes[g]) < cap
        ys = grouped(xs, w_gate, w_up, w_down, sizes)
        return jnp.where(keep[:, None], ys, jnp.zeros_like(ys))

    ops.moe_grouped_ffn = kept
    try:
        yield
    finally:
        ops.moe_grouped_ffn = grouped


def faults(arch, tokens: int) -> dict:
    """kind -> (configuration, context) of each fault the comparison must
    catch: the program built and run with that configuration inside that
    context has the fault planted.  ``tokens`` is a client step's."""
    none = contextlib.nullcontext
    return {
        "capacity": (arch, capacity_routing(tokens, arch.moe.top_k,
                                            arch.moe.n_experts)),
        "full_mask": (dataclasses.replace(arch, attn_period=tuple(
            dataclasses.replace(k, window=0) for k in arch.attn_period)),
            none()),
        "no_yarn": (dataclasses.replace(arch, attn_period=tuple(
            dataclasses.replace(k, rope=dataclasses.replace(
                k.rope, yarn_factor=0.0, attention_factor=1.0))
            for k in arch.attn_period)), none()),
    }


def compare(got: dict, after: dict, before: dict, ref) -> dict:
    """Numbers compared between the program's round (``got``: its host
    metrics; ``after``: its new params) and the reference's ``ref``, both
    from the params ``before`` (host pytrees in the reference's layout).
    ``param_gap`` is the worst leaf's distance to the reference's new
    params over the larger of that leaf's change in the reference and the
    median leaf's change: a state left unchanged reads 1."""
    import jax
    lp = np.asarray(got["client_losses"], np.float64)
    lr = np.asarray(ref.losses, np.float64)
    sp = np.asarray(got["scores"], np.float64)
    sr = np.asarray(ref.scores, np.float64)
    norm = lambda u, v: float(np.linalg.norm(
        (np.asarray(u, np.float64) - np.asarray(v, np.float64)).ravel()))
    leaves = list(zip(jax.tree.leaves(after), jax.tree.leaves(ref.params),
                      jax.tree.leaves(before)))
    change = [norm(r, b) for _, r, b in leaves]
    floor = max(float(np.median(change)), 1e-30)
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "score_gap": float(np.max(np.abs(sp - sr)) / np.max(np.abs(sr))),
        "param_gap": max(norm(a, r) / max(c, floor)
                         for (a, r, _), c in zip(leaves, change)),
        "tokens_dropped": float(np.sum(got["moe_dropped"])),
    }
