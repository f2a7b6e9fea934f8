"""The sparse decoder family at a toy size on the CPU: the program
(``TransformerLM`` through ``make_lm_train_step``, handed the architecture
whole) against the plain reference (reference_sparse_lm.py) with every kind of
layer — loss, per-leaf gradient norms, three-step parameter change, the count
of assignments on held experts; what the family answers; the new readers; and
the command end to end."""

import dataclasses
import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
sys.path.insert(0, BENCH)

import check  # noqa: E402
import experiment  # noqa: E402
import reference_sparse_lm as ref  # noqa: E402
from families import sparse_lm  # noqa: E402
from katib_tpu.models.architecture import architecture_config  # noqa: E402

CELL, CONFIG = experiment.load_cell("tiny-sparse.steady")
REAL = experiment.load_json("configs", "laguna-xs2-d5e32.json")


def _flat(tree):
    return {"/".join(str(getattr(k, "key", k)) for k in path): v
            for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.fixture(scope="module")
def program():
    """Three float32 steps of the program's own step at lr 1e-3."""
    from katib_tpu.parallel.train import make_lm_train_step

    config = dataclasses.replace(architecture_config(CONFIG, CELL["seq_len"]), dtype=jnp.float32)
    params, opt_state, step, put_batch = make_lm_train_step(config, None, 1e-3)
    p0 = jax.tree.map(jnp.copy, params)
    tokens, targets = ref.make_batch(CONFIG["vocab_size"], CELL["batch_size"], CELL["seq_len"])
    batch = put_batch(tokens, targets)
    losses, landed, grad_norm = [], [], None
    for i in range(3):
        params, opt_state, loss, counters = step(params, opt_state, *batch)
        losses.append(float(loss))
        landed.append(int(counters["landed"]))
        if i == 0:
            mu = next(s for s in jax.tree_util.tree_leaves(opt_state, is_leaf=lambda x: hasattr(x, "mu"))
                      if hasattr(s, "mu")).mu
            grad_norm = {k: float(v) / 0.1 for k, v in ref.leaf_norms(mu).items()}
    delta = {k: float(v) for k, v in ref.leaf_norms(jax.tree.map(jnp.subtract, params, p0)).items()}
    return {"p0": p0, "loss": losses, "landed": landed, "grad_norm": grad_norm, "delta_norm": delta}


def test_the_tiny_size_has_every_kind_of_layer():
    kinds = set(zip(CONFIG["layer_types"], CONFIG["mlp_layer_types"]))
    assert kinds == {("full_attention", "dense"), ("sliding_attention", "sparse"), ("full_attention", "sparse")}
    assert sorted(set(CONFIG["num_attention_heads_per_layer"])) == [6, 8]
    assert CONFIG["sliding_window"] < CELL["seq_len"]
    assert CELL["seq_len"] > CONFIG["rope_parameters"]["original_max_position_embeddings"]  # YaRN is live
    assert CONFIG["expert_share"]["first"] > 0  # a share that does not start at the first expert


def test_reference_starts_from_the_program_s_parameters(program):
    mine, theirs = _flat(ref.init_params(ref.SparseLM(CONFIG))), _flat(program["p0"])
    assert set(mine) == set(theirs)
    for name in theirs:
        assert mine[name].shape == theirs[name].shape, name
        np.testing.assert_allclose(mine[name], theirs[name], rtol=1e-6, atol=1e-7, err_msg=name)


def test_program_agrees_with_the_reference_on_loss_gradients_and_update(program):
    reference = ref.Reference(CONFIG, CELL["batch_size"], CELL["seq_len"]).run(1e-3)
    gaps = check.training_gaps(program, reference)
    assert gaps["loss_gap"] < 2e-5 and gaps["grad_norm_gap"] < 2e-4 and gaps["delta_norm_gap"] < 2e-3, gaps
    # no token dropped: every assignment to a held expert is counted on both sides
    assert program["landed"] == reference["landed"]
    assert 0 < program["landed"][0] < 4 * CELL["batch_size"] * CELL["seq_len"] * CONFIG["num_experts_per_tok"]


def test_the_controls_read_far_above_the_program(program):
    reference = ref.Reference(CONFIG, CELL["batch_size"], CELL["seq_len"]).run(1e-3)
    for variant in (dict(precision="float8"), dict(frozen=True)):
        gaps = check.training_gaps(
            ref.Reference(CONFIG, CELL["batch_size"], CELL["seq_len"], **variant).run(1e-3), reference)
        assert max(gaps.values()) > 1e-2, (variant, gaps)
    kept = ref.Reference(CONFIG, CELL["batch_size"], CELL["seq_len"], moments_on_host=True).run(1e-3)
    assert kept["loss"] == reference["loss"] and kept["delta_norm"] == reference["delta_norm"]


def test_yarn_frequencies_are_the_program_s_and_keep_the_fast_ones():
    from katib_tpu.models.transformer import rotary_frequencies

    group = REAL["rope_parameters"]["full_attention"]
    mine = ref.yarn_frequencies(64, group)
    config = architecture_config(REAL, 8192)
    np.testing.assert_allclose(
        mine, rotary_frequencies(64, group["rope_theta"], config.rotary_of("full").yarn), rtol=1e-6)
    plain = 1.0 / group["rope_theta"] ** (np.arange(32) * 2.0 / 64)
    assert mine[0] == pytest.approx(plain[0]) and mine[-1] == pytest.approx(plain[-1] / 64)
    assert np.all(np.diff(mine) < 0)


# -- what the family answers -----------------------------------------------------------------

def test_parameter_count_is_the_cut_s_and_the_published_model_s():
    counts = sparse_lm.lm_parameters(REAL)
    assert counts["total"] == 691_623_936                    # 11.07 GB at 16 B
    assert counts["experts"] == 4 * 32 * 3 * 2048 * 512      # 402.7 M
    whole = dict(REAL, **REAL["published"])                   # the uncut model: 33.4 B published
    assert sparse_lm.lm_parameters(whole)["total"] == pytest.approx(33.44e9, rel=2e-3)


def test_step_operations_are_pinned_and_made_of_the_issue_s_parts():
    assert sparse_lm.train_step_flops(REAL, 1, 8192) == pytest.approx(19.70e12, rel=1e-3)
    # forward MFLOP a token, by part (ISSUE 30): projections 344, dense 101, head 51, routed+shared+router 54
    parts = [sparse_lm.layer_parameters(REAL, i) for i in range(5)]
    per_token = lambda key: 2e-6 * sum(p.get(key, 0) for p in parts)
    assert per_token("attention") == pytest.approx(344 + 1.1, abs=0.5)  # and the gates
    assert per_token("dense") == pytest.approx(101, abs=1)
    assert per_token("router") + per_token("shared") + per_token("routed_read") == pytest.approx(54, abs=1)
    assert sparse_lm.attended_pairs(8192) == 8192 * 8193 / 2
    assert sparse_lm.attended_pairs(8192, 512) == 512 * 513 / 2 + 7680 * 512
    assert sparse_lm.attended_pairs(256, 512) == sparse_lm.attended_pairs(256)


def test_kernel_costs_name_every_kernel_the_program_names():
    import flops

    costs = sparse_lm.kernel_costs(REAL, 1, 8192)
    assert set(costs) == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "flash_window_fwd",
                          "flash_window_bwd_dq", "flash_window_bwd_dkv", "expert_gmm_fwd",
                          "expert_gmm_dlhs", "expert_gmm_dw"}
    # at equal head counts and no window the operations are flops.py's
    dense = flops.flash_attention_cost(1, 8192, 48, 128)
    assert costs["flash_fwd"][0] == pytest.approx(dense["forward"][0], rel=2e-4)
    assert costs["flash_bwd_dq"][0] == pytest.approx(dense["backward_each"][0], rel=2e-4)
    # the band is an eighth of the causal half, to first order, at 64 heads for 48
    assert costs["flash_window_fwd"][0] / costs["flash_fwd"][0] == pytest.approx(64 / 48 / 8 * 0.97, rel=0.02)
    # one grouped product: one expert's worth a token (8 x 32 / 256), one matrix
    assert costs["expert_gmm_fwd"][0] == 2.0 * 8192 * 2048 * 512
    for ops, nbytes in costs.values():
        assert ops > 0 and nbytes > 0


def test_the_8k_cell_s_traffic_is_the_one_its_issue_fixed():
    """ISSUE 30, section 4: batch 1 x 8192, 4000 steps, TPE over logUniform 3e-5..3e-3, a 5 s trace, one
    trial compared. The range is not the builder's to narrow: where the runs spread too widely over it, it
    is the program that has to become steadier (PERF.md section 6, PR 30)."""
    cell = experiment.load_json("workloads", "laguna-xs2-d5e32.steady-8k.json")
    assert cell["search_space"] == {"learning_rate": {"min": "3e-5", "max": "3e-3", "distribution": "logUniform"}}
    assert (cell["batch_size"], cell["seq_len"], cell["num_steps"], cell["chips"]) == (1, 8192, 4000, 1)
    assert cell["algorithm"]["algorithmName"] == "tpe" and cell["trace"] == {"seconds": 5}
    assert cell["check_trials"] == 1 and cell["window"]["opens_after"] == {"reports": 2}
    assert set(cell["limits"]) == {"loss_gap", "grad_norm_gap", "delta_norm_gap"}


def test_trial_parameters_hand_the_architecture_whole():
    fixed = sparse_lm.trial_parameters(CELL, CONFIG)
    assert list(fixed) == ["architecture", "seq_len", "batch_size", "num_steps", "tensor_parallel"]
    assert os.path.samefile(fixed["architecture"], os.path.join(BENCH, "configs", "tiny-sparse.json"))
    document = experiment.experiment_document("bench", CELL, CONFIG, 1)
    names = [p["name"] for p in document["parameters"]]
    assert names[0] == "learning_rate" and "architecture" in names
    assert not {"vocab_size", "embed_dim", "num_layers", "num_heads"} & set(names)


def test_validate_refuses_at_once_what_the_program_cannot_build(monkeypatch):
    sparse_lm.validate(CELL, CONFIG)
    with pytest.raises(ValueError, match="kv_lora_rank"):
        sparse_lm.validate(CELL, dict(CONFIG, kv_lora_rank=512))
    with pytest.raises(ValueError, match="shorter than the window"):
        sparse_lm.validate(dict(CELL, seq_len=4), CONFIG)
    # an installed program without the hand-off (the parent commit): a sentence, no trial
    monkeypatch.setitem(sys.modules, "katib_tpu.models.architecture", None)
    with pytest.raises(ValueError, match="cannot be handed an architecture whole"):
        sparse_lm.validate(CELL, CONFIG)


# -- the new readers ---------------------------------------------------------------------------

def _run(trace=None, spans=None):
    window = types.SimpleNamespace(t_open=100.0, t_close=150.0, seconds=50.0, steps=250)
    return types.SimpleNamespace(
        cell={"batch_size": 1, "seq_len": 8192, "chips": 1}, config=REAL, family=sparse_lm,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, window=window,
        trace=trace, spans=spans or {})


def test_kernels_roofline_sums_least_time_over_device_time():
    from readers import kernels_roofline
    import flops

    costs = sparse_lm.kernel_costs(REAL, 1, 8192)
    least = {k: flops.roofline_seconds(*costs[k], _run().peaks)[0] for k in costs}
    trace = {"op_seconds": {"%flash_fwd.3 = bf16[...] custom-call": 4 * least["flash_fwd"],
                            "%flash_bwd_dq.7 = ...": 4 * least["flash_bwd_dq"],
                            "%flash_window_fwd.9 = ...": 1.0, "%fusion.1 = ...": 5.0},
             "op_counts": {"%flash_fwd.3 = bf16[...] custom-call": 2, "%flash_bwd_dq.7 = ...": 2,
                           "%flash_window_fwd.9 = ...": 6, "%fusion.1 = ...": 9}}
    names = ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]
    assert kernels_roofline.read(_run(trace), kernels=names) == pytest.approx(50.0)
    assert kernels_roofline.read(_run(trace), kernels=["expert_gmm_fwd"]) is None
    assert kernels_roofline.read(_run(None), kernels=names) is None


def test_kernels_roofline_costs_the_grouped_products_at_the_rows_the_program_counted():
    from readers import kernels_roofline
    import flops

    fields = ["t_end", "seconds", "steps", "dispatch_s", "wait_s", "report_s", "store_s", "landed"]
    # the trace covers the window's first 5 s: two intervals of a quarter of the expected 4 x 8192 rows
    rows = [[102.0, 2.0, 5, 0.01, 1.9, 0.001, 0.001, 8192.0], [104.0, 2.0, 5, 0.01, 1.9, 0.001, 0.001, 8192.0],
            [140.0, 2.0, 5, 0.01, 1.9, 0.001, 0.001, 32768.0]]
    spans = {"t": [{"name": "steps", "start": 90.0, "end": 600.0,
                    "attrs": {"interval_fields": fields, "intervals": rows}}]}
    peaks = _run().peaks
    quarter = sparse_lm.kernel_costs(REAL, 1, 8192, landed=8192.0)["expert_gmm_fwd"]
    expected = sparse_lm.kernel_costs(REAL, 1, 8192)["expert_gmm_fwd"]
    assert quarter[0] == expected[0] / 4 and quarter[1] < expected[1]   # the held matrix is read whatever lands
    assert sparse_lm.kernel_costs(REAL, 1, 8192, landed=4 * 8192.0)["expert_gmm_fwd"] == expected
    op = "%expert_gmm_fwd.5 = ..."
    trace = {"window_s": 5.0, "op_seconds": {op: 24 * 2 * flops.roofline_seconds(*quarter, peaks)[0]},
             "op_counts": {op: 24}}
    names = ["expert_gmm_fwd", "expert_gmm_dlhs", "expert_gmm_dw"]
    assert kernels_roofline.read(_run(trace, spans), kernels=names, counted=["landed"]) == pytest.approx(50.0)
    assert kernels_roofline.read(_run(trace, spans), kernels=names) > 50.0    # at the expected load
    assert kernels_roofline.read(_run(trace), kernels=names, counted=["landed"]) is None  # nothing counted


def test_the_reference_keeps_its_moments_on_the_host_where_the_state_fills_the_device(monkeypatch):
    import reference_sparse_lm

    made = []
    monkeypatch.setattr(reference_sparse_lm, "Reference", lambda *a, **kw: made.append(kw))
    for limit in (None, 64 * 2**30, 16 * 2**30):   # the CPU says nothing; a v5e holds 16 GiB
        monkeypatch.setattr(sparse_lm, "device_memory_bytes", lambda: limit)
        sparse_lm.reference({"batch_size": 1, "seq_len": 8192}, REAL)
    assert [kw["moments_on_host"] for kw in made] == [False, False, True]   # 11.07 GB of state


def test_routing_load_reads_the_ledger_s_counters_and_nothing_where_there_are_none():
    from readers import routing_load

    fields = ["t_end", "seconds", "steps", "dispatch_s", "wait_s", "report_s", "store_s",
              "landed", "load_max", "load_mean"]
    rows = [[110.0 + i, 1.0, 5, 0.01, 0.9, 0.001, 0.001, 32000.0, 1200.0, 1000.0] for i in range(3)]
    rows.append([500.0, 1.0, 5, 0.01, 0.9, 0.001, 0.001, 1.0, 9999.0, 1.0])  # outside the window
    spans = {"t": [{"name": "steps", "start": 90.0, "end": 600.0,
                    "attrs": {"interval_fields": fields, "intervals": rows}}]}
    assert routing_load.read(_run(spans=spans)) == pytest.approx(1.2)
    bare = {"t": [{"name": "steps", "start": 90.0, "end": 600.0,
                   "attrs": {"interval_fields": fields[:7], "intervals": [r[:7] for r in rows]}}]}
    assert routing_load.read(_run(spans=bare)) is None
    assert routing_load.read(_run()) is None


@pytest.mark.parametrize("metric", ["train_step_mfu.sparse", "device_idle_share.sparse",
                                    "flash_window_fwd_roofline", "flash_window_dq_roofline",
                                    "flash_window_dkv_roofline", "flash_grouped_roofline",
                                    "expert_products_roofline", "expert_load_max_over_mean"])
def test_new_metrics_are_the_new_cell_s_alone(metric):
    spec = experiment.load_json("layer_metrics", f"{metric}.json")
    assert spec["workloads"] == ["laguna-xs2-d5e32.steady-8k"] and spec["moves"] == "train_tokens_per_s"
    costs = sparse_lm.kernel_costs(REAL, 1, 8192)
    named = spec.get("args", {}).get("kernels", []) + [spec.get("args", {}).get("kernel")]
    assert all(k in costs for k in named if k)


# -- the command end to end ---------------------------------------------------------------------

def test_the_command_runs_the_sparse_cell_end_to_end_on_the_cpu(monkeypatch, capsys):
    import run

    monkeypatch.setattr(run, "find_device", lambda chips: {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    code = run.main(["--workload", "tiny-sparse.steady", "--seed", "3000000019", "--seconds", "2", "--trace", "0"])
    out = capsys.readouterr()
    assert code == 0, out.err[-2000:]
    result = json.loads(out.out.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0, result["checks"]
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert result["window"]["steps"] == 5 * result["window"]["reports"]
