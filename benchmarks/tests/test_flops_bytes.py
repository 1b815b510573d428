import pytest

from benchmarks import flops_bytes as fb
from benchmarks import harness

MISTRAL = harness.load_json(harness.HERE, "configs", "mistral-7b-v0.3.json")
XL = harness.load_json(harness.HERE, "configs", "gpt2-xl.json")
JOB = harness.load_json(harness.HERE, "traffic", "pretrain-s1024.json")
llama, gpt2 = harness.family(MISTRAL), harness.family(XL)  # the counts live with the family


def test_one_mistral_layer_by_hand():
    # q 4096x4096, k and v 4096x1024 each, o 4096x4096, three 4096x14336.
    by_hand = 4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096 + 3 * 4096 * 14336
    assert by_hand == 218_103_808
    assert llama.layer_matmul_params(MISTRAL) == by_hand
    # key and value of one position: 2 x 16 layers x 8 heads x 128 x 2 bytes
    assert llama.kv_bytes_per_token(MISTRAL) == 65_536
    # 16 blocks with their two norms, the final norm and the head, in bf16
    assert llama.weight_bytes(MISTRAL) == 2 * (
        16 * (by_hand + 2 * 4096) + 4096 + 4096 * 32768)


def test_one_gpt2_xl_layer_by_hand():
    by_hand = 1600 * 4800 + 1600 * 1600 + 2 * 1600 * 6400
    assert by_hand == 30_720_000
    assert gpt2.layer_matmul_params(XL) == by_hand


def test_gpt2_xl_parameters_agree_with_the_program():
    from ray_tpu.models import gpt2 as program

    assert XL["assumed"]["padded_vocab_size"] == 50304
    cfg = gpt2.model_config(XL, JOB)
    assert gpt2.num_params(XL) == program.num_params(cfg) == 1_557_686_400


def test_train_operations_per_token_by_hand():
    assert JOB["seq_len"] == 1024
    matmul = 2 * (48 * 30_720_000 + 50304 * 1600)
    attention = 48 * 4 * 1600 * 1025 / 2
    assert gpt2.train_flops_per_token(XL, JOB) == 3 * (matmul + attention)
    # 7,534 tokens/s/chip on a 197 TFLOP/s chip is 37.5%: PR 24's reading
    share = gpt2.train_flops_per_token(XL, JOB) * 7534 / 197e12
    assert share == pytest.approx(0.375, abs=0.001)


def test_decode_step_is_memory_bound_and_under_its_roofline():
    peak = harness.peaks_for("TPU v5 lite")
    ops, nbytes = llama.decode_step(MISTRAL, batch=10, context_tokens=10 * 500)
    assert nbytes == llama.weight_bytes(MISTRAL) + 65_536 * (5000 + 10)
    share, bound = fb.roofline_pct(ops, nbytes, 0.036, peak)
    assert bound == "memory" and 20 < share < 30


def test_prefill_is_compute_bound():
    ops, nbytes = llama.prefill(MISTRAL, 512)
    _share, bound = fb.roofline_pct(ops, nbytes, 0.04, harness.peaks_for("TPU v5 lite"))
    assert bound == "compute"
    assert ops == pytest.approx(
        2 * 512 * 16 * 218_103_808 + 2 * 4096 * 32768 + 4 * 4096 * 16 * 512 * 513 / 2)


def test_an_unknown_device_is_an_error():
    with pytest.raises(SystemExit):
        harness.peaks_for("TPU v9")
