"""The hybrid cell (granite-4.0-h-small, serve.granite4h.decode): its work
counts by hand, its files found by name alone, and its check passing the
program, failing under planted faults and failing the float8 control."""
from __future__ import annotations

import json
from types import SimpleNamespace

import pytest

import harness
import work
import work_hybrid
from conftest import BENCH, run, small_checkout

CELL = "serve.granite4h.decode"
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

# the configuration at a size a CPU test holds: one whole period of 10
# layers at widths as small as the program's smoke configurations, 4 of 16
# router experts held
SMALL = {"hidden_size": 128, "num_attention_heads": 4,
         "num_key_value_heads": 2, "mamba_n_heads": 16, "mamba_d_head": 16,
         "mamba_d_state": 16, "mamba_chunk_size": 8, "num_local_experts": 4,
         "num_experts_per_tok": 4, "intermediate_size": 64,
         "shared_intermediate_size": 64, "vocab_size": 2048}
# prompts that end mid-chunk, so the SSD's tail path runs
SMALL_TRAFFIC = {"clients": 8, "batch_buckets": [8], "prompt_lens": [8, 13, 21],
                 "new_tokens": [4, 12], "new_token_levels": 5,
                 "think_s": [0.0, 0.02], "seq_bucket": 40,
                 "check_requests": 8}


@pytest.fixture
def hybrid(tmp_path):
    root = small_checkout(tmp_path)
    f = root / "bench" / "configs" / "granite-4.0-h-small.json"
    cfg = {**json.loads(f.read_text()), **SMALL}
    cfg["deployment"] = {**cfg["deployment"], "router_experts": 16,
                         "experts_held_first": 4}
    # the limits at this size, between their readings here: logits, the
    # program ~0.006, a state lost between steps ~0.013-0.023, the float8
    # control ~0.05-0.08; states, the program 0.023-0.033, the float8
    # control 0.18, a state lost 1.0
    cfg["limits"] = {"logit_err_mean": 0.015, "state_err_max": 0.08}
    f.write_text(json.dumps(cfg))
    t = root / "bench" / "traffic" / "decode-closed-128-long.json"
    t.write_text(json.dumps({**json.loads(t.read_text()), **SMALL_TRAFFIC}))
    return root


def _cfg(**kw):
    cfg = json.loads((BENCH / "configs" / "granite-4.0-h-small.json")
                     .read_text())
    return {**cfg, **kw}


def test_cell_found_by_its_files_alone():
    cell = harness.find_cell(BENCH.parent, CELL)
    assert cell.config["driver"] == "serve_hybrid"
    assert cell.traffic["batch_buckets"] == [cell.traffic["clients"]]
    assert cell.reference().__name__.endswith("granite_4_0_h_small_ref")
    names = {m["name"] for m in cell.per_layer}
    assert names == {"decode_mfu.granite4h", "prefill_mfu.granite4h",
                     "gmm_roofline.granite4h", "slot_roofline.granite4h",
                     "idle_share.granite4h"}
    assert {m["name"] for m in cell.end_to_end} == {
        "decode_tok_s", "itl_p95_ms", "ttft_p90_ms", "setup_s"}
    for m in names:
        assert callable(cell.metric_reader(m).read)


def test_published_sizes_and_the_cut():
    cfg = _cfg()
    pub = cfg["published"]
    assert (cfg["num_hidden_layers"], pub["num_hidden_layers"]) == (10, 40)
    assert cfg["layer_types"] == pub["layer_types"][:10]
    assert cfg["layer_types"].count("attention") == 1
    assert cfg["num_local_experts"] * cfg["deployment"]["chips_per_layer"] \
        == pub["num_local_experts"] == cfg["deployment"]["router_experts"]
    assert cfg["vocab_size"] * cfg["deployment"]["chips_per_layer"] \
        == pub["vocab_size"]


def test_hybrid_counts_by_hand():
    """A tiny configuration, every term written out."""
    cfg = {"hidden_size": 4, "num_attention_heads": 2,
           "num_key_value_heads": 1, "mamba_n_heads": 2, "mamba_d_head": 4,
           "mamba_d_state": 3, "mamba_n_groups": 1, "mamba_d_conv": 4,
           "layer_types": ["mamba", "attention"], "num_hidden_layers": 2,
           "num_local_experts": 2, "num_experts_per_tok": 2,
           "deployment": {"router_experts": 8}, "intermediate_size": 3,
           "shared_intermediate_size": 5, "vocab_size": 10}
    # di 8, conv 8 + 6 = 14; pairs held = T * 2 * 2 / 8 = T / 2
    assert work_hybrid.held_pairs(cfg, 4) == 2.0
    assert work_hybrid.held_experts(cfg, 1) == 1
    assert work_hybrid.held_experts(cfg, 40) == 2
    # 2 pairs x 3 x 2 x 4 x 3 flops; 2 experts x 36 weights x 2 B + 4x4x2x2
    assert work_hybrid.routed_experts(cfg, 4) == work.Work(144.0, 208.0)
    # ssm 2x4x3 = 24 plus conv 3 x 14 = 42, in float32, one mamba layer
    assert work_hybrid.state_row_bytes(cfg) == 264
    # decode: projections 2*T*(mamba 4*(8+14+2)+8*4 = 128, attn 4*(4+4)+4*4
    # = 48, shared 2*60, router 2*32); small f32 vectors of the mamba layer
    # 4*14 + 14 + 6 + 8 = 84, norms 2*2*4 + 4 = 20
    step = work_hybrid.decode_step(cfg, tokens=1, context=3)
    dense = work.Work(2.0 * (128 + 48 + 120 + 64),
                      (128 + 48 + 120) * 2 + (64 + 84 + 20) * 4)
    experts = 2 * work_hybrid.routed_experts(cfg, 1)
    recur = work.Work(4.0 * 24, 2.0 * 264)
    attn = work.Work(2 * 2.0 * 3 * 2 * 2, (3 + 1) * 2 * 2 * 2.0)
    head = work.Work(2.0 * 4 * 10, 10 * 4 * 2 + 10 * 4.0)
    assert step == dense + experts + recur + attn + head
    pre = work_hybrid.prefill(cfg, 3)
    dense3 = work.Work(3 * dense.flops, dense.bytes) \
        + 2 * work_hybrid.routed_experts(cfg, 3)
    want = (dense3 + work.Work(4.0 * 3 * 24, 264.0)
            + work.Work(2 * 2.0 * 3 * 2 * 2 * 2, 3 * 4 * 2.0)
            + work.Work(2.0 * 4 * 10, 10 * 4 * 2.0))
    assert pre == want
    assert work_hybrid.slot_writes(100).least_s(PEAK) == 200 / 819e9


def test_the_cells_step_is_bound_by_its_bytes():
    cfg = _cfg()
    assert work_hybrid.state_row_bytes(cfg) == 9 * (128 * 64 * 128
                                                    + 3 * 8448) * 4
    step = work_hybrid.decode_step(cfg, tokens=128, context=128 * 842)
    assert step.least_s(PEAK) == pytest.approx(step.bytes / 819e9)
    assert 14e9 < step.bytes < 15e9


def test_slot_metric_reads_the_counters_and_is_silent_without():
    reader = harness.load_module(BENCH / "metrics"
                                 / "slot_roofline.granite4h.py")
    summary = SimpleNamespace(module_s={"jit__install": 1e-3,
                                        "jit__move": 1e-3})
    ctx = SimpleNamespace(peak=PEAK, summary=summary, config=_cfg(),
                          counters={"state_bytes_installed": 819e6 / 4,
                                    "state_bytes_moved": 0})
    assert reader.read(ctx) == pytest.approx(25.0)
    ctx.counters = {}
    assert reader.read(ctx) is None


def _wrap_decode(r, change):
    eng = r.engine
    real = eng._decode

    def broken(params, cache, tokens, pos):
        logits, cache = real(params, cache, tokens, pos)
        return change(logits), cache
    eng._decode = broken


def half_the_batch(r):
    """The second half of the batch left out: its rows' logits never
    computed."""
    def change(logits):
        return logits.at[logits.shape[0] // 2:].set(0.0)
    _wrap_decode(r, change)


def forgotten_state(r):
    """The Mamba-2 state is not carried between steps: each decode step
    hands back a zero state."""
    import jax.numpy as jnp
    eng = r.engine
    real = eng._decode

    def broken(params, cache, tokens, pos):
        logits, new = real(params, cache, tokens, pos)
        return logits, {j: {b: {n: (jnp.zeros_like(a) if n == "ssm" else a)
                                for n, a in ce.items()}
                            for b, ce in per.items()}
                        for j, per in new.items()}
    eng._decode = broken


@pytest.mark.parametrize("fault", [None, half_the_batch, forgotten_state])
def test_check_passes_the_program_and_fails_its_faults(hybrid, fault):
    out = run(hybrid, CELL, seconds=2.0, after_setup=fault)
    assert out.result["correct"] is (fault is None), out.result["checks"]


def test_control_fails_where_the_program_passes(hybrid):
    out = run(hybrid, CELL, seconds=2.0, control=True)
    assert out.result["correct"], out.result["checks"]
    limits = {c["name"]: c["limit"] for c in out.checks}
    assert [c for c in out.control if c["value"] > limits[c["name"]]], \
        (out.control, limits)
