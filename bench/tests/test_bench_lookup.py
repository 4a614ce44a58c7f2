"""What a configuration names is found by name: the environment's plain
reference (``ref/<env>.py``), each network's backbone
(``ref/backbones/<kind>.py``: reference and counts), the program's
network config from every key, and a kernel's events by the kernel's
name. The counts and the reference's outputs are pinned to the values
the harness gave before these lookups existed (exact integers and
digests of the float32 arrays), so that the lookups moved no number."""
import collections
import hashlib
import pathlib
import types

import jax
import numpy as np
import pytest

import run
import tiny
from harness import catalog, counts, faults, job as job_mod, trace
from harness.ref import backbones
from harness.ref.core import Ref, env_module

HARNESS = pathlib.Path(__file__).resolve().parents[1] / "harness"
CC = ', custom_call_target="tpu_custom_call", operand_layout_constraints={}'

# (round_matmul_flops, policy step, AIP step, GAE ops, GRU kernel ops);
# ops as (count, sha256 of the ordered list of (flops, bytes), multiset)
GAE_F50 = (50, "82f192c6b90a55fb", {(204800, 512000): 50})
NO_OPS = (0, "4f53cda18c2baa0c", {})
PINNED_COUNTS = {
    "traffic10.f50": (2305685913600, 83712, 43008, GAE_F50, NO_OPS),
    "traffic10.f50.4chip": (2305685913600, 83712, 43008, GAE_F50, NO_OPS),
    "warehouse10.f50": (
        7039746048000, 282624, 118784, GAE_F50,
        (3280, "366c142bc4699988", {
            (40755200, 2104320): 800, (80076800, 2249344): 228,
            (160153600, 4300544): 850, (326041600, 13233920): 2,
            (640614400, 13535744): 600, (1282867200, 26841088): 600,
            (2282291200, 92337920): 100, (4576051200, 184138240): 100})),
}
# digests of Ref's outputs at float32, ``highest``: the state at
# initialisation, the GS collect, the AIP round and one inner step
PINNED_REF = {
    "fnn": ("8fb08f2e1ecb5831", "3339545ca4399631", "0bc4971a8b65a4a2",
            "249530f76329b442"),
    "gru": ("de1151539c161a57", "ef330c4deee7cb4d", "c89ed7d0f0ec84ab",
            "360ee30d4e2e7bed"),
}


def _ops(ops):
    ints = [(int(f), int(b)) for f, b in ops]
    assert [(float(f), float(b)) for f, b in ints] == list(ops)
    sha = hashlib.sha256(repr(ints).encode()).hexdigest()[:16]
    return len(ints), sha, dict(collections.Counter(ints))


def _digest(tree) -> str:
    h = hashlib.sha256()
    for x in jax.tree.leaves(tree):
        a = np.asarray(x)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("cell", sorted(PINNED_COUNTS))
def test_counts_are_pinned(cell):
    w = catalog.workload(cell)
    job = job_mod.make_job(w["config_file"], w["traffic_file"], w["chips"])
    info = Ref(job).info
    flops, pol, aip, gae, gru = PINNED_COUNTS[cell]
    r = counts.round_matmul_flops(job, info)
    assert r == float(flops) and int(r) == flops
    assert counts.policy_fwd_flops(job, info) == pol
    assert counts.aip_fwd_flops(job, info) == aip
    assert _ops(counts.gae_ops(job, info)) == gae
    assert _ops(counts.kernel_ops("gru", job, info)) == gru


@pytest.mark.parametrize("kind, make", [("fnn", tiny.job),
                                        ("gru", tiny.gru_job)])
def test_reference_outputs_are_pinned(kind, make):
    job = make()
    ref, key = Ref(job), run.seed_key(2 ** 31 + 11)
    with jax.default_matmul_precision("highest"):
        ials, aips = ref.init(key)
        kc, kt, _ = jax.random.split(jax.random.fold_in(key, 0), 3)
        data = ref.collect(ials["params"], kc)
        aip_round = ref.aip_round(aips, data, jax.random.split(kt, ref.n))
        step = ref.ials_step(ials, aip_round[0])
    got = (_digest((ials, aips)), _digest(data), _digest(aip_round),
           _digest(step))
    assert got == PINNED_REF[kind]


# -- lookup by name ---------------------------------------------------------------
@pytest.mark.parametrize("env", ["traffic", "warehouse"])
def test_environments_resolve_by_name(env):
    mod = env_module(env)
    assert mod.__name__ == f"harness.ref.{env}"
    assert mod.config_from_side(3).info().n_agents == 9
    assert Ref(tiny.job(env=env)).info.name == env


@pytest.mark.parametrize("kind", ["fnn", "gru"])
def test_backbones_resolve_by_name(kind):
    mod = backbones.get(kind)
    assert mod.__name__ == f"harness.ref.backbones.{kind}"
    for fn in ("init", "hidden0", "step", "sequence", "fwd_flops",
               "kernel_ops"):
        assert callable(getattr(mod, fn))


def test_unknown_env_or_kind_raises_with_the_file_to_add():
    with pytest.raises(KeyError, match="bench/harness/ref/no_env.py"):
        Ref(tiny.job(env="no_env"))
    with pytest.raises(KeyError, match="bench/harness/ref/no_env.py"):
        faults._cross_chip_mask(types.SimpleNamespace(
            info=types.SimpleNamespace(name="no_env")))
    path = "bench/harness/ref/backbones/ssm.py"
    job = tiny.job(policy={"kind": "ssm", "hidden": [16, 8],
                           "gru_hidden": 8})
    with pytest.raises(KeyError, match=path):
        Ref(job).init(run.seed_key(1))
    with pytest.raises(KeyError, match=path):
        counts.round_matmul_flops(job, Ref(tiny.job()).info)
    with pytest.raises(KeyError, match=path):
        counts.kernel_ops("ssm", tiny.job(), Ref(tiny.job()).info)
    with pytest.raises(KeyError, match="bench/harness/ref/backbones/a-b"):
        backbones.get("a-b")


def test_kernel_ops_of_a_kind_no_network_uses_are_none():
    info = Ref(tiny.job()).info
    assert counts.kernel_ops("gru", tiny.job(), info) == []
    assert counts.kernel_ops("fnn", tiny.gru_job(),
                             Ref(tiny.gru_job()).info) == []


def test_exchange_dropped_takes_the_environments_mask():
    cfg = env_module("traffic").config_from_side(2)
    runner = types.SimpleNamespace(info=cfg.info(), env_cfg=cfg,
                                   n_shards=4)
    mask = np.asarray(faults._cross_chip_mask(runner))
    # one agent a chip: each of the 2 x 2 grid's 8 inner lanes crosses
    assert mask.shape == (4, 1, 1, 4) and mask.sum() == 16 - 8
    runner.n_shards = 1
    assert np.asarray(faults._cross_chip_mask(runner)).min() == 1.0
    cfg = env_module("warehouse").config_from_side(2)
    with pytest.raises(NotImplementedError, match="cross_chip_mask"):
        faults._cross_chip_mask(types.SimpleNamespace(
            info=cfg.info(), env_cfg=cfg, n_shards=4))


def test_program_takes_every_network_key():
    _, _, pc, ac, _, _ = job_mod.program(tiny.gru_job(), outer_rounds=1)
    assert (pc.kind, pc.hidden, pc.gru_hidden) == ("gru", (16, 8), 8)
    assert (ac.kind, ac.hidden, ac.gru_hidden) == ("gru", (8, 8), 8)
    job = tiny.job(aip={"kind": "fnn", "hidden": [8, 8], "gru_hidden": 8,
                        "state_size": 16})
    with pytest.raises(TypeError, match="state_size"):
        job_mod.program(job, outer_rounds=1)


def test_kernel_kind_is_the_kernels_name():
    # as the program's kernels appear in a chip trace: the GAE kernel
    # under vmap (the one-chip cells) and under grad (the recorded trace)
    named = {"%gae_fwd.3": "gae", "%gae_bwd.1": "gae", "%gru_bwd.6": "gru",
             "%gru_fwd": "gru", "%ssd_fwd.1": "ssd",
             "%vmap_gae_fwd_.2": "gae", "%vmap_vmap_gae_fwd__": "gae",
             "%transpose_jvp_gae_bwd__.1": "gae",
             "%vmap_flash_attention_fwd_.4": "flash_attention"}
    for instr, kind in named.items():
        name = (f"{instr} = f32[16,100]{{1,0}} custom-call(f32[16,100]"
                f"{{1,0}} %a, f32[16,100]{{1,0}} %b)" + CC)
        assert trace.kernel_kind(name) == kind
        d = trace.Device("/device:TPU:0", [], [(name, 0, 250)])
        assert d.kernel_events(kind) == 1
        assert d.kernel_seconds(kind) == 250e-9
    other = ("%custom-call.2 = f32[8]{0} custom-call(f32[8]{0} %x), "
             'custom_call_target="Sharding"')
    assert trace.kernel_kind(other) is None


def test_lookup_modules_hold_no_environment_or_kind():
    """The harness's shared modules name no environment and branch on no
    backbone kind: those live in files found by name."""
    names = ("traffic", "warehouse", "powergrid", "supplychain")
    for rel in ("ref/core.py", "ref/nets.py", "counts.py", "faults.py",
                "trace.py"):
        text = (HARNESS / rel).read_text()
        for word in names:
            assert word not in text.lower(), (rel, word)
        for kind in ('"gru"', '"fnn"', "'gru'", "'fnn'"):
            assert kind not in text, (rel, kind)
