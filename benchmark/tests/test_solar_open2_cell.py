"""The Solar-Open2 cell through the rest of a run (``run_cell``), without the
look for a chip, at the tiny size of ``tiny_solar_open2.py``: sound it comes
out ``correct`` with every request served through chunked prefill, states
and the full layer's keys and values; with a served token altered where it is
produced it does not; and the control, the reference in the precision below
put in the program's place, reads no nearer than the sound program."""

import json

from benchmark import run, spec
from benchmark.tests.tiny_solar_open2 import tiny_cell

CELL = "serve_solar_open2_longdoc_mixed"
SEED = 3_000_000_019


def test_configuration_and_cell_load_as_the_issue_states():
    cell = spec.load_cell(CELL)
    model, top = cell.config["model"], cell.config
    assert cell.chips == 1 and cell.mode == "serve"
    assert [m["name"] for m in cell.end_to_end] == ["tpot_p95_ms", "setup_s"]
    # every published width, under the catalog's key and in the model
    linear = top["linear_attn_config"]
    assert (top["hidden_size"], top["num_attention_heads"],
            top["num_key_value_heads"], top["head_dim"],
            linear["num_heads"], linear["head_dim"],
            linear["short_conv_kernel_size"], top["num_experts_per_tok"],
            top["moe_intermediate_size"], top["n_shared_experts"]) == (
        4096, 64, 8, 128, 64, 128, 4, 8, 1280, 1)
    assert (model["emb_dim"], model["n_heads"], model["n_kv_groups"],
            model["attn_head_dim"], model["linear_heads"],
            model["linear_head_dim"], model["linear_conv"],
            model["n_experts_per_tok"], model["hidden_dim"],
            model["n_shared_experts"]) == (
        4096, 64, 8, 128, 64, 128, 4, 8, 1280, 1)
    assert model["n_routed_experts"] == 320          # the router's width
    assert len(model["experts_held"]) == top["n_routed_experts"] == 20
    assert top["deployment"]["chips_sharing_each_layer"] == 16
    assert set(top["reduced"]) == {"num_hidden_layers", "n_routed_experts",
                                   "vocab_size", "max_position_embeddings"}
    assert top["use_rope"] is False and model["positional"] == "none"
    assert (top["use_gqa_gate"], top["kda_allow_neg_eigval"]) == (
        model["attn_out_gate"], model["linear_neg_eigval"]) == (True, True)
    # the program's own preset, cut to this chip's share, is the same model
    from building_llm_from_scratch_tpu.configs import ModelConfig, get_config

    published = get_config("solar_open2", "250B", dtype="bf16",
                           target_context_length=None)
    assert published.num_params() == 250_287_794_944
    assert published.num_params(active=True) == 14_735_682_304
    chip = published.replace(n_layers=4, vocab_size=24576,
                             context_length=33792,
                             experts_held=tuple(range(20)))
    assert ModelConfig(**model) == chip
    assert chip.num_params() == 2_050_060_864
    assert cell.traffic["engine"]["kv_policy"] == {"prefill_chunk": 512}
    assert model["context_length"] % cell.traffic["engine"]["kv_policy"][
        "prefill_chunk"] == 0
    arrivals = cell.traffic["arrivals"]
    assert arrivals["share_of_knee"] == 0.8
    assert abs(arrivals["rate_per_s"]
               - arrivals["share_of_knee"] * arrivals["knee_per_s"]) < 0.06
    names = {m["name"] for m in cell.per_layer}
    assert len(names) == 11 and all(n.endswith(".longdoc") for n in names)
    assert "hybrid_decode_roofline_pct.longdoc" in names


def test_least_bytes_of_a_tick_count_the_model_by_layer_kind():
    """The new reader's count against the program's own: everything outside
    the routed experts is what the configuration holds less its experts and
    its embedding, in bfloat16; an expert, a position and a state are the
    sizes the issue states."""
    from benchmark.readers import hybrid_decode_bytes as hb
    from building_llm_from_scratch_tpu.configs import ModelConfig

    model = spec.load_cell(CELL).config["model"]
    cfg = ModelConfig(**model)
    outside = (cfg.num_params() - cfg.vocab_size * cfg.emb_dim
               - cfg.n_layers * 20 * 3 * cfg.emb_dim * cfg.hidden_dim)
    assert hb.dense_bytes_per_tick(model) == 2 * outside
    assert hb.expert_bytes(model) == 31_457_280
    assert hb.kv_bytes_per_position(model) == 4096
    assert hb.state_bytes_per_row(model) == 2 * (4_194_304 + 147_456)
    assert hb.tick_bytes(model, 0, 0, 0, 0) == hb.dense_bytes_per_tick(model)


def test_serve_sound_then_token_altered(monkeypatch, capsys):
    cell = tiny_cell(CELL)
    cell.config["model"].update(dtype="fp32")
    sound = run.run_cell(cell, SEED, 2.0, False, None,
                         control=cell.config["precision"]["below"])
    assert sound["correct"] and sound["failed"] == 0 and sound["attempted"] > 0
    said = [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]
    compared = next(d["reference_compared"] for d in said
                    if "reference_compared" in d)
    from benchmark.reference.solar_open2 import LEAST_COMPARED

    assert compared["share"] >= LEAST_COMPARED
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
            # every other token from the third on: at this size some
            # positions are router near-ties and are not compared
            self, slot, req, (tok + 1) % vocab
            if len(req.output_ids) >= 2 and len(req.output_ids) % 2 == 0
            else tok, gen))
    broken = run.run_cell(cell, SEED, 2.0, False, None)
    assert not broken["correct"] and broken["failed"] == 0
