"""Mellum2-12B-A2.5B's mechanisms at a small size on the CPU, against the
plain float32 reference of the benchmark (``bench/configs/
mellum2-12b-a2.5b.py``, ``bench/reference/lm_round.py``) on seeded weights:

- the loss and gradients of the mixed window/full attention stack with
  YaRN on its full layers and dropless held experts;
- one ``folb_round(agg_backend="flat")`` (float32 master weights, bfloat16
  compute, as the benchmark cell runs it), on new params and scores,
  within the cell's limits;
- the share test: the held-expert outputs of the shares {0-3} and {4-7}
  add up to the uncut reference's MoE output, attention counted once;
- faults (capacity routing, a full mask on the window layers, plain RoPE
  in place of YaRN) each exceed the cell's limits, as do the reference
  in float8 (the control) and on half of each client's batch;
- the weights drawn by the reference map into the program's tree and
  back.

Every kernel (splash attention, grouped FFN, FOLB aggregation) runs in
Pallas interpret mode here.
"""
from __future__ import annotations

import contextlib
import copy
import dataclasses
import importlib.util
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.drivers import lm_round as drv  # noqa: E402
from bench.reference import lm_round as ref_round  # noqa: E402
from repro.fed.distributed import RoundConfig, folb_round  # noqa: E402
from repro.models import attention, layers  # noqa: E402
from repro.models import model as model_lib  # noqa: E402
from repro.models import moe as moe_lib  # noqa: E402

LIMITS = json.loads((ROOT / "bench" / "limits" / "mellum2-folb-8k.json")
                    .read_text())
K, B, S = 2, 2, 64
LR, MU, E = 1e-3, 0.01, 2


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load(ROOT / "bench" / "configs" / "mellum2-12b-a2.5b.py",
            "mellum2_reference")


def small_json(**over) -> dict:
    """The benchmark's configuration file at a test size: d 128, one period
    (3 window layers of 16 keys, 1 full layer with YaRN over 32 original
    positions), 4 of 8 experts held, top 4."""
    cfg = json.loads((ROOT / "bench" / "configs" / "mellum2-12b-a2.5b.json")
                     .read_text())
    cfg = copy.deepcopy(cfg)
    cfg.update(hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
               head_dim=32, vocab_size=256, num_experts=4,
               num_experts_router=8, num_experts_per_tok=4,
               moe_intermediate_size=128, sliding_window=16)
    cfg["rope_parameters"]["full_attention"][
        "original_max_position_embeddings"] = 32
    cfg.update(over)
    return cfg


ref_params = drv.ref_layout


@pytest.fixture(scope="module")
def setup():
    """(config, program configuration, the reference's seeded weights in
    its own layout, the same in the program's, a round's batch)."""
    cfg = small_json()
    arch = drv.arch_config(cfg)
    init = REF.init_params(cfg, [3])
    params = jax.tree.map(jnp.asarray, drv.program_layout(arch, init))
    rng = np.random.default_rng(7)
    seqs = rng.integers(0, cfg["vocab_size"], (K, B, S + 1)).astype(np.int32)
    batch = {"tokens": jnp.asarray(seqs[..., :-1]),
             "labels": jnp.asarray(seqs[..., 1:])}
    return cfg, arch, init, params, batch


def _round(arch, params, batch):
    rc = RoundConfig(algo="folb", n_clients=K, local_steps=E, lr=LR, mu=MU,
                     remat=True, agg_backend="flat", agg_dtype="bfloat16")
    step = jax.jit(lambda p, b: folb_round(arch, rc, p, b))
    new, m = step(params, batch)
    return new, {k: np.asarray(v) for k, v in m.items()}


def _reference(cfg, init, batch, dtype="float32"):
    ref = ref_round.run(REF, cfg, jax.device_put(init),
                        np.asarray(batch["tokens"]),
                        np.asarray(batch["labels"]), LR, MU, E, dtype=dtype)
    ref.params = jax.device_get(ref.params)
    return ref


def _gaps(cfg, arch, init, params, batch, fault=None):
    """The cell's compared numbers for one round of the program (or of it
    with a fault of ``drv.faults`` planted) against the reference from the
    same weights."""
    arch_f, planted = fault or (arch, contextlib.nullcontext())
    with planted:
        new, got = _round(arch_f, params, batch)
    return drv.compare(got, jax.device_get(ref_params(arch, new)), init,
                       _reference(cfg, init, batch))


@pytest.fixture(scope="module")
def sound(setup):
    return _gaps(*setup)


def _fails(gaps) -> list:
    return [k for k, lim in LIMITS.items() if gaps.get(k, 0) > lim]


class TestAgainstReference:
    def test_loss_and_grads_match_float32(self, setup):
        cfg, arch, _, params, batch = setup
        f32 = dataclasses.replace(arch, param_dtype="float32")
        tok, lab = batch["tokens"][0], batch["labels"][0]
        lp, gp = jax.jit(jax.value_and_grad(
            lambda p: model_lib.loss_fn(f32, p, {"tokens": tok,
                                                 "labels": lab},
                                        remat=True)))(params)
        with jax.default_matmul_precision("highest"):
            lr_, gr = jax.jit(jax.value_and_grad(
                lambda p: REF.loss(cfg, p, tok, lab)))(ref_params(arch, params))
        assert abs(float(lp) - float(lr_)) <= 1e-5 * abs(float(lr_))
        got = jax.tree.leaves(ref_params(arch, gp))
        want = jax.tree.leaves(gr)
        for g, w in zip(got, want):
            scale = float(jnp.max(jnp.abs(w))) + 1e-12
            assert float(jnp.max(jnp.abs(g - w))) <= 2e-4 * scale

    def test_flat_round_matches_within_limits(self, sound):
        assert not _fails(sound), sound
        assert sound["tokens_dropped"] == 0

    def test_share_outputs_add_up_to_uncut_layer(self, setup):
        """Two chips' shares of one block ({0-3} and {4-7} of 8 experts):
        their outputs, less one copy of the residual and attention that
        each computes alike, give the uncut reference block."""
        cfg, arch, _, params, batch = setup
        f32 = dataclasses.replace(arch, param_dtype="float32")
        whole = dataclasses.replace(
            f32, moe=dataclasses.replace(f32.moe, n_held=8, held_offset=0))
        lp = model_lib.init_params(whole, jax.random.PRNGKey(5))
        block = jax.tree.map(lambda x: x[0, 0], lp["period"][0])
        kind = whole.attn_period[0]
        x = jax.random.normal(jax.random.PRNGKey(6), (B, S, 128)) * 0.5

        def share(offset):
            m = dataclasses.replace(whole.moe, n_held=4, held_offset=offset)
            part = dataclasses.replace(whole, moe=m)
            bp = dict(block, moe=dict(block["moe"], **{
                k: block["moe"][k][offset:offset + 4]
                for k in ("w_gate", "w_up", "w_down")}))
            out, _ = jax.jit(lambda v: model_lib._apply_kind_block(
                part, kind, bp, v))(x)
            return out

        attn_only = x + jax.jit(lambda v: attention.attention_kind_forward(
            whole, kind, block["attn"],
            layers.apply_norm(whole, block["attn_norm"], v)))(x)
        total = share(0) + share(4) - attn_only
        rcfg = dict(small_json(), num_experts=8)
        p = ref_params(whole, lp)["layers"][0]
        eps = rcfg["rms_norm_eps"]
        with jax.default_matmul_precision("highest"):
            def ref_block(v):
                v = v + REF.attention(rcfg, "sliding_attention", p,
                                      REF.rmsnorm(v, p["attn_norm"], eps))
                return v + REF.experts(rcfg, p,
                                       REF.rmsnorm(v, p["moe_norm"], eps))
            want = jax.vmap(ref_block)(x)
        err = float(jnp.max(jnp.abs(total - want)))
        assert err <= 1e-4 * float(jnp.max(jnp.abs(want))), err


def test_reference_window_blocks_read_every_reachable_key(setup):
    """The reference's window layers give each query block only the keys
    it can reach; with blocks shorter than the window the result is the
    one-block attention over every key."""
    cfg, arch, _, params, batch = setup
    p = ref_params(arch, params)["layers"][0]
    a = jax.random.normal(jax.random.PRNGKey(8), (S, 128))
    with jax.default_matmul_precision("highest"):
        whole = REF.attention(cfg, "sliding_attention", p, a, q_block=S)
        blocks = REF.attention(cfg, "sliding_attention", p, a, q_block=8)
    assert float(jnp.max(jnp.abs(whole - blocks))) <= 1e-5 * float(
        jnp.max(jnp.abs(whole)))


class TestFaults:
    """Each fault, planted in the program for the round, reads over at
    least one of the cell's limits."""

    @pytest.mark.parametrize("fault", ["capacity", "full_mask", "no_yarn"])
    def test_fault_exceeds_limits(self, setup, sound, fault):
        gaps = _gaps(*setup, fault=drv.faults(setup[1], B * S)[fault])
        assert _fails(gaps), (fault, gaps, sound)

    @pytest.mark.parametrize("kind", ["control", "half_batch"])
    def test_reference_in_the_programs_place_exceeds_limits(
            self, setup, sound, kind):
        """The reference in float8 (the control), or with each client on
        half of its sequences, in the program's place."""
        cfg, arch, init, params, batch = setup
        low = _reference(cfg, init, batch, "float8_e4m3fn") \
            if kind == "control" else \
            _reference(cfg, init, {k: v[:, :B // 2] for k, v in batch.items()})
        gaps = drv.compare({"client_losses": low.losses, "scores": low.scores,
                            "moe_dropped": np.zeros(1)}, low.params, init,
                           _reference(cfg, init, batch))
        assert _fails(gaps), (kind, gaps, sound)

    def test_capacity_fault_drops_rows(self, setup):
        """Every token alike, so each expert it routes to gets all B x S
        of them, past the fault's capacity of 1.25 x the mean load: under
        the fault the held layer's output changes, outside it it does
        not."""
        cfg, arch, _, params, batch = setup
        block = jax.tree.map(lambda x: x[0, 0], params["period"][0])
        x = jnp.broadcast_to(
            jax.random.normal(jax.random.PRNGKey(9), (128,)), (B, S, 128))
        layer = lambda: jax.jit(lambda v: moe_lib.moe_held_forward(
            arch, block["moe"], v)[0])(x)
        sound_out = layer()
        with drv.faults(arch, B * S)["capacity"][1]:
            capped = layer()
        assert float(jnp.max(jnp.abs(layer() - sound_out))) == 0.0
        assert float(jnp.max(jnp.abs(capped - sound_out))) > 0.0


def test_program_layout_inverts_ref_layout():
    """The reference's weights of two periods map into the program's tree
    (its structure and shapes) and back unchanged."""
    from repro.launch import steps
    cfg = small_json(num_hidden_layers=8)
    cfg["layer_types"] = cfg["layer_types"] * 2
    arch = dataclasses.replace(drv.arch_config(small_json()), n_layers=8)
    init = REF.init_params(cfg, [5])
    prog = drv.program_layout(arch, init)
    shapes = steps.params_shape(arch)
    assert jax.tree.structure(prog) == jax.tree.structure(shapes)
    for a, b in zip(jax.tree.leaves(prog), jax.tree.leaves(shapes)):
        assert a.shape == b.shape
    for a, b in zip(jax.tree.leaves(ref_params(arch, prog)),
                    jax.tree.leaves(init)):
        assert np.array_equal(np.asarray(a), b)


def test_held_rows_cover_every_assignment():
    """The sorted buffer holds every (token, expert) assignment that can
    land on a held expert, rounded to the kernel's row tile."""
    arch = drv.arch_config(small_json())
    assert moe_lib.held_rows(arch, 128) == 128 * 4
    full = drv.arch_config(json.loads(
        (ROOT / "bench" / "configs" / "mellum2-12b-a2.5b.json").read_text()))
    assert moe_lib.held_rows(full, 16384) == 16384 * 8
    assert full.block_pattern() == (("moe", 3), ("moe", 1))
    with pytest.raises(NotImplementedError):
        model_lib.init_cache(full, 1, 16)


def test_param_specs_shard_the_held_experts():
    """The period stack's leaves get the path rules of their names: in
    expert mode the held experts' axis is the one sharded over 'model'."""
    from jax.sharding import PartitionSpec as P
    from repro.configs import get_config
    from repro.launch import steps
    from repro.sharding import specs
    cfg = get_config("mellum2-12b-a2.5b")
    ps = steps.params_shape(cfg)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    spec = specs.param_specs(cfg, ps, mesh)
    run0 = spec["period"][0]
    assert run0["moe"]["w_up"] == P(None, None, "model", None, None)
    assert run0["moe"]["router"]["w"] == P(None, None, None, None)
    assert run0["attn"]["wq"]["w"] == P(None, None, None, "model")
    assert ps["period"][0]["moe"]["w_up"].shape == (1, 3, 8, 2304, 896)
