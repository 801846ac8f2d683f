"""One vocabulary of phases (tracing.spans.PHASES) on every device op of a
training step: each Pallas kernel goes by a `name=` that starts with a
phase, and the compiled step of every step builder carries the named
scopes in its instructions' `op_name`, with next to nothing left outside.

CPU only: what is checked is metadata (names and counts), never a time.
"""

import ast
import collections
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest

from glom_tpu.tracing.spans import (
    DEVICE_PHASES,
    EVABYTE_DEVICE_PHASES,
    HOST_PHASES,
    KIMI_DEVICE_PHASES,
    LAGUNA_DEVICE_PHASES,
    LAGUNA_INNER_SCOPES,
    LM_DEVICE_PHASES,
    LM_KERNELS,
    OURO_DEVICE_PHASES,
    PHASES,
    SAMBAY_DEVICE_PHASES,
    SCAN_KERNELS,
)
from glom_tpu.utils.config import GlomConfig, TrainConfig

KERNELS = pathlib.Path(__file__).resolve().parent.parent / "glom_tpu" / "kernels"
N_PALLAS_CALLS = 25


def _pallas_call_names():
    """(file, line, name= literal or None) of every pallas_call site."""
    sites = []
    for path in sorted(KERNELS.glob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "pallas_call"):
                name = next((kw.value for kw in node.keywords if kw.arg == "name"), None)
                literal = (name.value if isinstance(name, ast.Constant)
                           and isinstance(name.value, str) else None)
                sites.append((path.name, node.lineno, literal))
    return sites


class TestKernelNames:
    def test_every_pallas_call_site_has_a_literal_name(self):
        sites = _pallas_call_names()
        assert len(sites) == N_PALLAS_CALLS
        assert [s for s in sites if s[2] is None] == []

    def test_names_are_unique_lower_snake(self):
        names = [s[2] for s in _pallas_call_names()]
        assert len(set(names)) == len(names)
        assert all(re.fullmatch(r"[a-z][a-z0-9]*(_[a-z0-9]+)*", n) for n in names)

    def test_names_start_with_a_phase_and_say_their_direction(self):
        for fname, line, name in _pallas_call_names():
            # the language models' are held by name, below
            if fname not in ("flash_attention.py", "selective_scan.py"):
                assert any(name == p or name.startswith(p + "_") for p in DEVICE_PHASES), (
                    fname, line, name)
            assert {"fwd", "bwd"} & set(name.split("_")), (fname, line, name)

    def test_the_language_models_kernels_are_the_vocabularys_and_no_routes_name(self):
        """Every site of kernels/flash_attention.py goes by a name of
        LM_KERNELS and every name has a site; each begins with `attn_`, so
        none reads as a GLOM phase's kernel (`loop_*`, `ffw_*`,
        `consensus_*`: what `route_kernels` forbids on the route `lm_xla`)."""
        sites = [name for fname, _, name in _pallas_call_names() if fname == "flash_attention.py"]
        assert sorted(sites) == sorted(LM_KERNELS)
        for name in LM_KERNELS:
            assert name.startswith("attn_")
            assert not any(name == p or name.startswith(p + "_") for p in DEVICE_PHASES), name

    def test_the_scans_kernels_are_the_vocabularys_and_no_routes_name(self):
        """Every site of kernels/selective_scan.py goes by a name of
        SCAN_KERNELS and every name has a site; each begins with the scope it
        runs in, `selective_scan`, which is no GLOM phase and not `attn_flash`
        (whose calls the attention readers sum)."""
        sites = [name for fname, _, name in _pallas_call_names() if fname == "selective_scan.py"]
        assert sorted(sites) == sorted(SCAN_KERNELS)
        assert "selective_scan" in SAMBAY_DEVICE_PHASES
        for name in SCAN_KERNELS:
            assert name.startswith("selective_scan_")
            assert not any(name == p or name.startswith(p + "_") for p in DEVICE_PHASES), name

    def test_vocabulary_is_host_then_device_without_repeats(self):
        assert PHASES == HOST_PHASES + DEVICE_PHASES
        assert len(set(PHASES)) == len(PHASES)
        assert all(p.startswith("host_") for p in HOST_PHASES)


# --------------------------------------------------- scopes in compiled steps

CFG = GlomConfig(dim=32, levels=3, image_size=16, patch_size=4)
_SKIP_OPCODES = ("parameter", "constant", "get-tuple-element", "tuple", "bitcast")


def phase_counts(hlo_text: str) -> collections.Counter:
    """Instructions of a compiled program by the innermost phase in their
    `op_name` (the reduction of benchmark/reduce_phases.py, on text).
    Instructions the compiler made itself carry no op_name and are not the
    program's to name."""
    counts = collections.Counter()
    for line in hlo_text.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        op = re.search(r" = [^=]*? ([a-z][a-z0-9\-]*)\(", line)
        if not m or not m.group(1) or not op or op.group(1) in _SKIP_OPCODES:
            continue
        phases = [t for t in re.findall(r"[A-Za-z0-9_]+", m.group(1))
                  if t in DEVICE_PHASES]
        counts[phases[-1] if phases else "(none)"] += 1
    return counts


def _single_device_step(use_pallas: bool, with_grad_norm: bool):
    from glom_tpu.train.trainer import create_train_state, make_train_step

    tcfg = TrainConfig(batch_size=8, use_pallas=use_pallas)
    state, opt = create_train_state(jax.random.PRNGKey(0), CFG, tcfg, None)
    step = make_train_step(CFG, tcfg, opt, with_grad_norm=with_grad_norm)
    return jax.jit(step), state


def _manual_step(zero_stage: int):
    from glom_tpu.parallel import DistributedTrainer
    from glom_tpu.utils.config import MeshConfig

    tcfg = TrainConfig(batch_size=8, use_pallas=True, zero_stage=zero_stage)
    trainer = DistributedTrainer(CFG, tcfg, MeshConfig(data=4))
    assert trainer.use_manual and trainer.zero_stage == zero_stage
    return trainer._step, trainer.state


def _gspmd_step():
    from glom_tpu.parallel import DistributedTrainer
    from glom_tpu.utils.config import MeshConfig

    tcfg = TrainConfig(batch_size=8, use_pallas=False)
    trainer = DistributedTrainer(CFG, tcfg, MeshConfig(data=4))
    assert not trainer.use_manual
    return trainer._step, trainer.state


BUILDERS = {
    # builder -> (make, the device phases its step must carry)
    "make_train_step.scan": (
        lambda: _single_device_step(False, True),
        {"noise", "image_to_tokens", "loop", "bottom_up", "top_down", "consensus",
         "mean_update", "reconstruction", "optimizer", "step_metrics"}),
    "make_train_step.scan_fast_variant": (
        lambda: _single_device_step(False, False),
        {"noise", "image_to_tokens", "loop", "bottom_up", "top_down", "consensus",
         "mean_update", "reconstruction", "optimizer"}),
    "make_train_step.level_major": (
        lambda: _single_device_step(True, True),
        {"noise", "image_to_tokens", "loop", "bottom_up", "top_down",
         "consensus_update", "reconstruction", "optimizer", "step_metrics"}),
    "make_manual_train_step.dp4": (
        lambda: _manual_step(0),
        {"noise", "image_to_tokens", "loop", "bottom_up", "top_down",
         "consensus_update", "reconstruction", "optimizer", "step_metrics"}),
    "make_manual_zero_train_step.dp4": (
        lambda: _manual_step(1),
        {"noise", "image_to_tokens", "loop", "bottom_up", "top_down",
         "consensus_update", "reconstruction", "grad_reduce", "optimizer",
         "step_metrics"}),
    "gspmd.dp4": (
        _gspmd_step,
        {"noise", "image_to_tokens", "loop", "bottom_up", "top_down", "consensus",
         "mean_update", "reconstruction", "optimizer", "step_metrics"}),
}


@pytest.mark.parametrize("builder", sorted(BUILDERS))
def test_compiled_step_carries_every_phase(builder):
    make, expected = BUILDERS[builder]
    if "dp4" in builder and len(jax.devices()) < 4:
        pytest.skip("needs 4 (virtual) devices")
    step, state = make()
    img = jnp.zeros((8, CFG.channels, CFG.image_size, CFG.image_size), jnp.float32)
    text = step.lower(state, img, jax.random.PRNGKey(1)).compile().as_text()
    counts = phase_counts(text)
    named = sum(counts.values())
    assert named > 100, counts
    missing = expected - set(counts)
    assert not missing, (missing, counts)
    assert not ({"step_metrics"} & set(counts)) or "step_metrics" in expected, counts
    assert counts["(none)"] < 0.05 * named, counts


# ------------------------------------------ the language-model families' tuples

FAMILIES = {"hybrid_lm": LM_DEVICE_PHASES, "sambay": SAMBAY_DEVICE_PHASES,
            "laguna": LAGUNA_DEVICE_PHASES, "kimi_linear": KIMI_DEVICE_PHASES,
            "evabyte": EVABYTE_DEVICE_PHASES, "ouro": OURO_DEVICE_PHASES}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_familys_tuple_is_its_own_and_shares_names_only_where_they_mean_the_same(family):
    phases = FAMILIES[family]
    assert len(set(phases)) == len(phases)
    assert all(re.fullmatch(r"[a-z][a-z0-9]*(_[a-z0-9]+)*", p) for p in phases)
    assert not set(phases) & set(PHASES)                 # none reads as a phase of GLOM's
    assert phases[0] == "embed" and phases[-1] == "lm_head_loss"
    assert not set(phases) & set(LAGUNA_INNER_SCOPES)    # an inner scope is no phase
    assert not set(LM_KERNELS) & set(phases)


def test_lagunas_scopes_counters_and_inner_scopes_are_registered():
    """The routed part's four scopes are the second family's (one code), the
    two attention scopes SambaY's names for the same two masks; `rope` and
    `attn_gate` are inner scopes; the records' counters are the routed part's
    four and the attentions' two."""
    from glom_tpu.models import hybrid_lm, laguna, sambay

    assert set(LAGUNA_DEVICE_PHASES) - set(LM_DEVICE_PHASES) - set(SAMBAY_DEVICE_PHASES) == {
        "dense_mlp"}
    assert {"moe_router", "moe_dispatch", "moe_experts", "moe_combine", "moe_shared"} <= (
        set(LAGUNA_DEVICE_PHASES) & set(LM_DEVICE_PHASES))
    assert LAGUNA_INNER_SCOPES == ("rope", "attn_gate")
    assert set(laguna.ATTENTION_SCOPE.values()) == {"window_attention", "full_attention"} <= (
        set(sambay.ATTENTION_SCOPE.values()))
    assert laguna.COUNTERS == hybrid_lm.STACK_COUNTERS + (
        "attn_key_blocks_window", "attn_key_blocks_full", "swiglu_backward_staged")
    # the relu2 shared expert's counter is the first family's alone
    assert hybrid_lm.COUNTERS == hybrid_lm.STACK_COUNTERS + ("shared_backward_staged",)
    assert set(laguna.COUNTERS[-3:-1]) <= set(sambay.COUNTERS)
    # `laguna.swiglu` is the three families' that call it; the two others run no SwiGLU of it
    assert "swiglu_backward_staged" not in hybrid_lm.COUNTERS + sambay.COUNTERS


def test_kimi_linears_scopes_and_counters_are_registered():
    """The routed part's scopes, `moe_shared`, `embed` and `lm_head_loss` are
    the other families' on purpose (one code; `moe_routed_time_pct.train`'s
    reader reads the cell unedited), `dense_mlp` Laguna's; the delta rule's
    three scopes and the latent attention's are its own, and no family's
    attention or scan scope is borrowed for them. The records' counters are
    the routed part's, the kept forward's, the latent layers' key blocks and
    the delta rule's three."""
    from glom_tpu.models import hybrid_lm, kimi_linear

    own = set(KIMI_DEVICE_PHASES) - set(LM_DEVICE_PHASES) - set(LAGUNA_DEVICE_PHASES)
    assert own == {"kda_in", "kda_scan", "kda_out", "latent_attention"}
    assert not own & (set(SAMBAY_DEVICE_PHASES) | set(DEVICE_PHASES) | set(HOST_PHASES))
    assert {"moe_router", "moe_dispatch", "moe_experts", "moe_combine", "moe_shared"} <= (
        set(KIMI_DEVICE_PHASES) & set(LM_DEVICE_PHASES) & set(LAGUNA_DEVICE_PHASES))
    assert kimi_linear.COUNTERS == hybrid_lm.STACK_COUNTERS + (
        "attn_key_blocks_full", "kda_chunks", "kda_log_decay_min", "kda_forward_kept",
        "swiglu_backward_staged")


def test_evabytes_scopes_and_counters_are_registered():
    """`embed`, `dense_mlp` and `lm_head_loss` are the other families' on
    purpose; a layer's first half is four scopes of its own (projections and
    rotation, the chunk summariser, EVA attention alone, the out-projection
    with the float32 add), and no family's attention scope is borrowed for
    them. The attention kernels are the language models' two, under their
    names. The records' counters are the kept forward's, the two key
    segments' blocks, the summary keys formed and the prediction heads."""
    from glom_tpu.models import evabyte

    own = set(EVABYTE_DEVICE_PHASES) - set(LAGUNA_DEVICE_PHASES)
    assert own == {"eva_in", "eva_summary", "eva_attention", "eva_out"}
    assert not own & (set(LM_DEVICE_PHASES) | set(SAMBAY_DEVICE_PHASES) | set(KIMI_DEVICE_PHASES)
                      | set(DEVICE_PHASES) | set(HOST_PHASES))
    assert set(EVABYTE_DEVICE_PHASES) & set(LAGUNA_DEVICE_PHASES) == {
        "embed", "dense_mlp", "lm_head_loss"}
    assert evabyte.COUNTERS == ("attn_forward_kept", "attn_key_blocks_local",
                                "attn_key_blocks_summary", "eva_summary_keys", "lm_pred_heads",
                                "swiglu_backward_staged")
    assert all(name.startswith("attn_") for name in LM_KERNELS)


def test_ouros_scopes_and_counters_are_registered():
    """`embed`, `dense_mlp` and `lm_head_loss` are the other families' on
    purpose, and `full_attention` is SambaY's and Laguna's name for the causal
    mask without a window (here the scores alone). The loop's own are five:
    the layer's two projection scopes, the norms on the branches' outputs, the
    norm that closes a pass and the exit gate; no family's scope is borrowed
    for them. The records' counters are the loop's two, the kept forward's,
    the key blocks', the exit distribution's two and the staged SwiGLU's."""
    from glom_tpu.models import ouro

    own = set(OURO_DEVICE_PHASES) - set(LAGUNA_DEVICE_PHASES)
    assert own == {"ouro_in", "ouro_out", "sandwich_norm", "ut_close", "exit_gate"}
    assert not own & (set(LM_DEVICE_PHASES) | set(SAMBAY_DEVICE_PHASES) | set(KIMI_DEVICE_PHASES)
                      | set(EVABYTE_DEVICE_PHASES) | set(DEVICE_PHASES) | set(HOST_PHASES))
    assert set(OURO_DEVICE_PHASES) & set(LAGUNA_DEVICE_PHASES) == {
        "embed", "full_attention", "dense_mlp", "lm_head_loss"}
    assert ouro.COUNTERS == ("ut_steps", "layer_applications", "attn_forward_kept",
                             "attn_key_blocks_full", "exit_entropy", "exit_mass_last",
                             "swiglu_backward_staged")


def test_every_op_of_lagunas_rotation_lies_under_rope_inside_an_attention_scope():
    """The rotation runs three times a layer on q and on k: forward, in the
    layer's recomputation, and transposed (a `custom_vjp` whose backward opens
    `rope` itself). In the compiled step of the tiny preset each of its ops
    (the tables' sines and cosines, which nothing else in the model takes, and
    whatever reads the [head_dim, head_dim] permutation) carries `rope` inside
    `window_attention` or `full_attention`; none falls under no scope."""
    from glom_tpu.train.trainer import create_train_state, default_optimizer, make_train_step
    from glom_tpu.utils.presets import get_preset

    preset = get_preset("laguna-tiny")
    cfg, tcfg = preset.model, preset.train
    opt = default_optimizer(tcfg)
    state, _ = create_train_state(jax.random.PRNGKey(0), cfg, tcfg, opt)
    ids = jnp.zeros((tcfg.batch_size, cfg.seq_len), jnp.int32)
    text = jax.jit(make_train_step(cfg, tcfg, opt)).lower(
        state, ids, jax.random.PRNGKey(0)).compile().as_text()
    square = rf"f32\[{cfg.head_dim},{cfg.head_dim}\]"
    permutations = set(re.findall(rf"(%[\w.\-]+) = {square}\S* constant\(", text))
    assert permutations
    inside = re.compile(r"\b(window|full)_attention\b.*\brope\b")
    forms = collections.Counter()
    for line in text.splitlines():
        op = re.search(r" = [^=]*? ([a-z][a-z0-9\-]*)\(([^)]*)\)", line)
        if not op or not (op.group(1) in ("sine", "cosine")
                          or permutations & set(re.findall(r"%[\w.\-]+", op.group(2)))):
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        assert name and inside.search(name.group(1)), line
        if op.group(1) == "dot":
            path = name.group(1)
            forms["recomputed" if "rematted_computation" in path
                  else "backward" if "transpose(" in path else "forward"] += 1
    # q and k of every layer, in each of the three passes
    assert forms == {form: 2 * cfg.num_hidden_layers
                     for form in ("forward", "recomputed", "backward")}, forms
