"""The plain references and their controls at a size a test run can hold.

Serving: tokens that the float32 reference itself would serve read a gap of
exactly 0; the w8a8 control put in the program's place reads a gap above
the toy cell's limit somewhere among a few hundred positions.  Training:
the control and each fault a step can have read far above a sound
float32 run against itself.
"""

import json
import os

import numpy as np
import pytest

import chipbench_helpers as helpers

TOY_LLM = {
    "hidden_size": 32, "intermediate_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 2, "vocab_size": 4096, "rope_theta": 10000.0, "sliding_window": 24,
}


@pytest.fixture(scope="module")
def served():
    """Four prompts, each continued greedily by the reference itself."""
    import jax.numpy as jnp

    from chipbench.reference import llm

    rng = np.random.default_rng(3)
    cases = [{"prompt": rng.integers(0, 4096, size=n).tolist(), "tokens": []} for n in (12, 20, 28, 33)]
    for _ in range(48):
        ids = np.zeros((len(cases), 96), np.int32)
        for r, c in enumerate(cases):
            seq = c["prompt"] + c["tokens"]
            ids[r, : len(seq)] = seq
        hidden, head = llm.forward_logits(TOY_LLM, 11, jnp.asarray(ids))
        for r, c in enumerate(cases):
            at = len(c["prompt"]) + len(c["tokens"]) - 1
            c["tokens"].append(int(np.asarray(llm.logit_rows(hidden[None][r][at : at + 1], head)).argmax()))
    return cases


def test_reference_tokens_read_no_gap_and_control_reads_one(served):
    from chipbench.reference import llm

    rows = llm.served_gaps(TOY_LLM, 11, served, pad_to=96, control=True)
    gaps = [g for row in rows for g in row["gaps"]]
    control = [g for row in rows for g in row["control_gaps"]]
    assert len(gaps) == 4 * 48
    assert max(gaps) < 1e-4
    assert max(control) > 0.02 and sum(g > 0 for g in control) >= 3


def test_an_altered_token_reads_a_wide_gap(served):
    from chipbench.reference import llm

    broken = [dict(c, tokens=[(t + 1) % 4096 for t in c["tokens"][:1]] + c["tokens"][1:]) for c in served[:1]]
    rows = llm.served_gaps(TOY_LLM, 11, broken, pad_to=96)
    assert rows[0]["gaps"][0] > 1.0


def test_weights_do_not_depend_on_what_else_is_made():
    import jax

    from chipbench import weights

    whole = jax.jit(lambda words: weights.llm_params_tree(dict(TOY_LLM), words))(weights.seed_words(2**31 + 9))
    one = weights.llm_layer(TOY_LLM, 2**31 + 9, 1)
    assert np.array_equal(np.asarray(whole["layer_1"]["mlp"]["down"]["kernel"]), np.asarray(one["mlp/down"]))
    other = weights.llm_layer(TOY_LLM, 2**31 + 10, 1)
    assert not np.array_equal(np.asarray(one["mlp/down"]), np.asarray(other["mlp/down"]))


@pytest.fixture(scope="module")
def toy_resnet():
    with open(os.path.join(helpers.DATA, "tiny-resnet.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("fault,number,least", [
    ({"quant": "int8"}, "grad_norm_gap", 0.05),
    ({"half_batch": True}, "grad_norm_gap", 0.5),
    ({"frozen": True}, "change_norm_gap", 0.999),
])
def test_training_control_and_faults_read_far_above_a_sound_run(toy_resnet, fault, number, least):
    from chipbench.reference import resnet

    sound = resnet.follow(toy_resnet, 5)
    again = resnet.compare(resnet.follow(toy_resnet, 5), sound)
    assert max(again.values()) < 1e-5
    broken = resnet.compare(resnet.follow(toy_resnet, 5, **fault), sound)
    assert broken[number] > least


def test_reference_layout_is_the_programs(toy_resnet):
    import jax
    import jax.numpy as jnp

    from chipbench.reference import resnet
    from k8s_device_plugin_tpu.models.resnet import ResNet

    net = ResNet(stage_sizes=tuple(toy_resnet["stage_sizes"]), num_classes=toy_resnet["num_classes"],
                 width=toy_resnet["width"])
    theirs = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3))))
    params, stats = jax.eval_shape(lambda: resnet.init(toy_resnet, 1))
    assert jax.tree.map(lambda x: x.shape, theirs["params"]) == jax.tree.map(lambda x: x.shape, params)
    assert jax.tree.map(lambda x: x.shape, theirs["batch_stats"]) == jax.tree.map(lambda x: x.shape, stats)


def test_reference_step_agrees_with_the_program_in_float32(toy_resnet):
    """The program's own step in float32 against the plain reference: the
    two are the same arithmetic, so every compared number is rounding."""
    import jax
    import jax.numpy as jnp
    import optax

    from chipbench.reference import resnet
    from k8s_device_plugin_tpu.models.resnet import ResNet
    from k8s_device_plugin_tpu.models.train import TrainState, make_train_step

    m = toy_resnet
    net = ResNet(stage_sizes=tuple(m["stage_sizes"]), num_classes=m["num_classes"], width=m["width"],
                 dtype=jnp.float32, norm_dtype=jnp.float32)
    tx = optax.sgd(m["learning_rate"], momentum=m["momentum"])
    params0, stats0 = resnet.init(m, 9)
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params0, opt_state=tx.init(params0), batch_stats=stats0)
    step = jax.jit(make_train_step(net, tx))
    batch = resnet.make_batch(m, 9)
    program = {"losses": []}
    with jax.default_matmul_precision("highest"):
        for i in range(3):
            state, loss = step(state, batch)
            program["losses"].append(float(loss))
            if i == 0:
                program["grad_norms"] = resnet.leaf_norms(state.opt_state[0].trace)
    program["change_norms"] = resnet.leaf_norms(jax.tree.map(lambda a, b: a - b, state.params, params0))
    numbers = resnet.compare(program, resnet.follow(m, 9))
    assert max(numbers.values()) < 2e-3, numbers


# ---- the controls through the harness's own limits and its one test ----


def _limited(numbers: dict, limits: dict) -> dict:
    return {k: {"value": v, "limit": limits.get(k)} for k, v in numbers.items() if k not in ("seed", "run")}


def _chip_cases():
    """(name, kind of cell's limits, numbers, the verdict it has to get) for every
    reading kept from the chip, and for the faults that need no run."""
    with open(os.path.join(helpers.DATA, "chip_readings.json")) as f:
        kept = json.load(f)
    cases = []
    for row in kept["serve"]["sound"]:
        cases.append((f"serve-sound-{row['run']}", "serve", row, True))
    for row in kept["serve"]["control_int8"]:
        cases.append((f"serve-int8-{row['run']}", "serve", row, False))
    largest = kept["train"]["sound_largest_of_12_seeds"]
    cases.append(("train-sound-largest", "train", dict(largest, unmoved_leaves=0), True))
    for name in ("control_int8", "fault_half_batch"):
        for row in kept["train"][name]:
            cases.append((f"train-{name}-{row['seed']}", "train", dict(row, unmoved_leaves=0), False))
    # A state left unchanged reads 1 by the measure itself; one leaf left
    # where it was reads 1 on that leaf alone, under the worst-leaf limit.
    cases.append(("train-frozen", "train", dict(largest, change_norm_gap=1.0, change_median_gap=1.0, unmoved_leaves=161), False))
    cases.append(("train-one-leaf-unmoved", "train", dict(largest, change_norm_gap=1.0, unmoved_leaves=1), False))
    return cases


@pytest.mark.parametrize("name,kind,numbers,verdict", _chip_cases(), ids=[c[0] for c in _chip_cases()])
def test_chip_readings_against_the_cells_limits(name, kind, numbers, verdict):
    """The cells' committed limits lie between what sound runs and what the
    control and the faults read on the chip at the cells' own sizes."""
    from chipbench.stats import judged

    conf = {"serve": "mistral7b-d16", "train": "resnet50-b128"}[kind]
    with open(os.path.join(helpers.REPO, "chipbench", "configs", f"{conf}.json")) as f:
        correct = json.load(f)["correct"]
    limits = correct["limits"] if kind == "train" else {"gap_max": correct["gap_max"], "wrong_length": 0}
    assert judged(_limited(numbers, limits)) is verdict


def test_served_control_is_judged_by_the_same_limit_and_is_not_correct(served):
    """run.py's own comparison: the reference's own tokens pass a limit that
    the w8a8 control, read at the same positions, fails."""
    from types import SimpleNamespace

    from chipbench import run
    from chipbench.reference import llm
    from chipbench.stats import judged

    rows = llm.served_gaps(TOY_LLM, 11, served, pad_to=96, control=True)
    cell = SimpleNamespace(config={"correct": {"gap_max": 0.01}})
    compared, controls = run.serve_compared(cell, [], served, {"rows": rows, "seconds": 1.0})
    assert judged(compared) is True
    assert controls["control_int8"]["gap_max"]["limit"] == 0.01
    assert judged(controls["control_int8"]) is False


def test_one_leaf_left_unmoved_is_counted(toy_resnet):
    from chipbench.reference import resnet

    sound = resnet.follow(toy_resnet, 5)
    leaf = max(sound["change_norms"], key=sound["change_norms"].get)
    broken = dict(sound, change_norms=dict(sound["change_norms"], **{leaf: 0.0}))
    numbers = resnet.compare(broken, sound)
    assert numbers["unmoved_leaves"] == 1 and numbers["change_norm_gap"] == pytest.approx(1.0)
    assert resnet.compare(resnet.follow(toy_resnet, 5, frozen=True), sound)["unmoved_leaves"] == len(sound["change_norms"])
