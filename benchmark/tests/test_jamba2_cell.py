"""The Jamba2 cell through the rest of a run (``run_cell``), without the look
for a chip, at the tiny size of ``tiny_jamba2.py``: sound it comes out
``correct`` with every request served through chunked prefill, states and
the attention layer's keys and values; with a served token altered where it
is produced it does not; and the control, the reference in the precision
below put in the program's place, reads no nearer than the sound program."""

import json

from benchmark import run, spec
from benchmark.tests.tiny_jamba2 import tiny_cell

CELL = "serve_jamba2_3b_chat_wide"
SEED = 3_000_000_019
#: the catalog's ``config`` for AI21-Jamba2-3B (model-configs guide), key for
#: key; ``max_position_embeddings`` is the one the file changes
CATALOG = {
    "attn_layer_offset": 7, "attn_layer_period": 14,
    "expert_layer_offset": 1, "expert_layer_period": 2,
    "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
    "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba",
    "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": 28, "num_key_value_heads": 1,
    "num_logits_to_keep": 1, "rms_norm_eps": 1e-06, "sliding_window": None,
    "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536}


def test_configuration_and_cell_load_as_the_issue_states():
    cell = spec.load_cell(CELL)
    model, top = cell.config["model"], cell.config
    assert cell.chips == 1 and cell.mode == "serve"
    assert [m["name"] for m in cell.end_to_end] == ["tpot_p95_ms", "setup_s"]
    # every key of the source but the one cut, and that one beside it
    assert top["reduced"] == ["max_position_embeddings"]
    assert {k: top[k] for k in CATALOG} == dict(
        CATALOG, max_position_embeddings=3072)
    assert top["published"]["max_position_embeddings"] == 262144
    assert top["deployment"]["chips_sharing_each_layer"] == 1
    # the widths in the model are the source's
    assert (model["emb_dim"], model["n_heads"], model["n_kv_groups"],
            model["attn_head_dim"], model["hidden_dim"], model["n_layers"],
            model["vocab_size"], model["ssm_inner"], model["ssm_state"],
            model["ssm_dt_rank"], model["ssm_conv"]) == (
        2560, 20, 1, 128, 8192, 28, 65536,
        top["mamba_expand"] * top["hidden_size"], 16, 160, 4)
    period = top["attn_layer_period"]
    assert model["layer_kinds"] == [
        "full" if l == top["attn_layer_offset"] else "ssm"
        for l in range(period)]
    assert model["positional"] == "none" and model["tie_embeddings"]
    assert model["rmsnorm_eps"] == top["rms_norm_eps"]
    # the program's own preset, cut to the context, is the same model
    from building_llm_from_scratch_tpu.configs import ModelConfig, get_config

    published = get_config("jamba2", "3B", dtype="bf16",
                           target_context_length=None)
    assert published.context_length == 262144
    assert published.num_params() == 3_029_337_472
    chip = published.replace(context_length=3072)
    assert ModelConfig(**model) == chip
    assert chip.num_params() == 3_029_337_472      # no positions to count
    engine = cell.traffic["engine"]
    assert engine["n_slots"] == 192 and engine["spec_k"] == 0
    assert engine["kv_policy"] == {"prefill_chunk": 512}
    assert model["context_length"] % 512 == 0
    assert (cell.traffic["prompt"]["max"] + cell.traffic["output"]["max"]
            <= model["context_length"])
    assert cell.traffic["check"] == {"n": 6, "min_tokens": 300}
    assert cell.traffic["greedy_every"] == 8
    arrivals = cell.traffic["arrivals"]
    assert arrivals["share_of_knee"] == 0.8
    assert abs(arrivals["rate_per_s"]
               - arrivals["share_of_knee"] * arrivals["knee_per_s"]) < 0.06
    names = {m["name"] for m in cell.per_layer}
    assert len(names) == 12 and all(n.endswith(".widechat") for n in names)
    assert {"ssm_decode_roofline_pct.widechat",
            "state_rows_touched_over_needed.widechat",
            "chunk_roofline_pct.widechat"} <= names
    # what a slot holds, and the whole of it at 192 slots
    from building_llm_from_scratch_tpu.serving.kvcache import KVCachePolicy

    bps = KVCachePolicy(prefill_chunk=512).bytes_per_slot(chip, 3072)
    assert bps["state_bytes"] == 26 * (327_680 + 30_720)
    assert bps["kv_bytes"] == 3072 * 2 * 512
    assert 192 * bps["total_bytes"] + 2 * chip.num_params() == 8_451_787_520


def test_least_bytes_of_a_tick_count_the_model_by_layer_kind():
    """The new readers' counts against the program's own: everything outside
    the embedding is what the configuration holds less its embedding; a
    mixer, a position and a state are the sizes the issue states."""
    from benchmark.readers import chunk_roofline
    from benchmark.readers import ssm_decode_bytes as sb
    from building_llm_from_scratch_tpu.configs import ModelConfig

    model = spec.load_cell(CELL).config["model"]
    cfg = ModelConfig(**model)
    assert sb.ssm_mixer_params(model) == 41_241_792
    assert sb.attention_mixer_params(model) == 13_762_560
    assert sb.block_params(model) == (
        cfg.num_params() - cfg.vocab_size * cfg.emb_dim) == 2_861_565_312
    assert sb.dense_bytes_per_tick(model) == 2 * cfg.num_params()
    assert sb.kv_bytes_per_position(model) == 512
    assert sb.state_bytes_per_row(model) == 2 * (327_680 + 30_720)
    assert sb.tick_bytes(model, 0, 0, 0) == sb.dense_bytes_per_tick(model)
    assert sb.tick_bytes(model, 10, 26, 1) == (
        sb.dense_bytes_per_tick(model) + 5120 + 26 * 716_800 + 5120)
    # a full chunk is bound by its products, a short one by the weights
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    full = chunk_roofline.chunk_seconds_at_peak(model, 512, peaks)
    assert abs(full - 2 * 2_861_565_312 * 512 / 197e12) < 1e-12
    short = chunk_roofline.chunk_seconds_at_peak(model, 32, peaks)
    assert abs(short - 2 * cfg.num_params() / 819e9) < 1e-12


def test_serve_sound_then_token_altered(monkeypatch, capsys):
    cell = tiny_cell(CELL, greedy_every=2)     # half the requests checked
    cell.config["model"].update(dtype="fp32")
    sound = run.run_cell(cell, SEED, 2.0, False, None,
                         control=cell.config["precision"]["below"])
    assert sound["correct"] and sound["failed"] == 0 and sound["attempted"] > 0
    said = [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]
    compared = next(d["reference_compared"] for d in said
                    if "reference_compared" in d)
    assert compared["share"] == 1.0 and compared["positions"] >= 50
    control = next(d["control"] for d in said if "reference_s" in d)
    program = next(d["window"] for d in said if "window" in d)
    assert control is not None and control >= program[
        "served_logit_widest_gap"]
    assert program["tick_phases_ms"]["prefill"] > 0       # the chunk program

    from building_llm_from_scratch_tpu.serving.engine import DecodeEngine

    real = DecodeEngine._accept_token
    vocab = cell.config["model"]["vocab_size"]
    monkeypatch.setattr(
        DecodeEngine, "_accept_token",
        lambda self, slot, req, tok, gen: real(
            self, slot, req, (tok + 1) % vocab
            if len(req.output_ids) == 2 else tok, gen))
    broken = run.run_cell(cell, SEED, 2.0, False, None)
    assert not broken["correct"] and broken["failed"] == 0
