"""The Cohere2 MoE cell through the rest of a run (``run_cell``), without the
look for a chip, at the tiny size of ``tiny_cohere2.py``: sound it comes out
``correct`` with every request served through chunked prefill and rings;
with a served token altered where it is produced it does not; and the
control, the reference in the precision below put in the program's place,
reads no nearer than the sound program."""

import json

from benchmark import run, spec
from benchmark.tests.tiny_cohere2 import tiny_cell

CELL = "serve_command_a_plus_rag_mixed"
SEED = 3_000_000_019


def test_configuration_and_cell_load_as_the_issue_states():
    cell = spec.load_cell(CELL)
    model, top = cell.config["model"], cell.config
    assert cell.chips == 1 and cell.mode == "serve"
    assert [m["name"] for m in cell.end_to_end] == ["tpot_p95_ms", "setup_s"]
    # every published width, under the catalog's key and in the model
    assert (top["hidden_size"], top["intermediate_size"], top["head_dim"],
            top["num_attention_heads"], top["num_key_value_heads"],
            top["num_experts_per_tok"], top["num_shared_experts"],
            top["sliding_window"], top["rope_theta"]) == (
        4096, 4096, 128, 128, 8, 8, 4, 4096, 50000)
    assert (model["emb_dim"], model["hidden_dim"], model["attn_head_dim"],
            model["n_heads"], model["n_kv_groups"],
            model["n_experts_per_tok"], model["n_shared_experts"],
            model["sliding_window"], model["rope_base"]) == (
        4096, 4096, 128, 128, 8, 8, 4, 4096, 50000.0)
    assert model["n_routed_experts"] == 128
    assert len(model["experts_held"]) == top["num_experts"] == 8
    assert set(top["reduced"]) == {"num_hidden_layers", "num_experts",
                                   "vocab_size", "max_position_embeddings"}
    # the program's own preset, cut to this chip's share, is the same model
    from building_llm_from_scratch_tpu.configs import ModelConfig, get_config

    assert ModelConfig(**model) == get_config(
        "command_a_plus", "218B", dtype="bf16",
        target_context_length=None).replace(
            n_layers=4, vocab_size=32768, context_length=20480,
            experts_held=tuple(range(8)))
    assert cell.traffic["engine"]["kv_policy"] == {"prefill_chunk": 512}
    arrivals = cell.traffic["arrivals"]
    assert abs(arrivals["rate_per_s"]
               - arrivals["share_of_knee"] * arrivals["knee_per_s"]) < 0.06
    assert {m["name"] for m in cell.per_layer} >= {
        "decode_device_ms.rag", "chunk_device_ms.rag",
        "expert_rows_max_over_mean.rag", "moe_decode_roofline_pct.rag",
        "ttft_p50_ms.rag", "ttft_p95_ms.rag"}


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
    assert compared["share"] >= 0.5
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
            # every other token from the third on: at this size four
            # positions in ten are router near-ties and are not compared
            self, slot, req, (tok + 1) % vocab
            if len(req.output_ids) >= 2 and len(req.output_ids) % 2 == 0
            else tok, gen))
    broken = run.run_cell(cell, SEED, 2.0, False, None)
    assert not broken["correct"] and broken["failed"] == 0
