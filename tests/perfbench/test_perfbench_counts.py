"""counts.py against hand-worked operations and bytes."""
import json
import os

import pytest

from benchmarks.harness import counts, manifest as M, peaks

BERT = json.load(open(os.path.join(M.BENCH_DIR, "configs", "bert-base-uncased.json")))
MISTRAL = json.load(open(os.path.join(M.BENCH_DIR, "configs", "mistral-7b-v0.3-d16.json")))


def test_bert_base_forward_flops_per_token_by_hand():
    # per layer: q,k,v,o = 4 * 2*768*768; mlp = 2 * 2*768*3072; attention at
    # S=512: scores + values = 2 * 2*768*512
    layer = 4 * 2 * 768 * 768 + 2 * 2 * 768 * 3072 + 2 * 2 * 768 * 512
    assert layer == 4_718_592 + 9_437_184 + 1_572_864
    head = 2 * 768 * 768 + 2 * 768 * 30522
    assert counts.bert_forward_flops_per_token(BERT, 512) == 12 * layer + head
    assert counts.bert_train_flops_per_token(BERT, 512) == 3 * (12 * layer + head)
    # 8,192 tokens a step at 95,569 tok/s (ledger, PR 22) is 34.3 % of 197 TFLOP/s
    mfu = counts.bert_train_flops_per_token(BERT, 512) * 95569 / 197e12
    assert mfu == pytest.approx(0.343, abs=0.002)


def test_mistral_d16_parameters_and_decode_flops_by_hand():
    layer = 4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096 + 3 * 4096 * 14336 + 2 * 4096
    assert layer == 218_103_808 + 8192 == 218_112_000
    total = 16 * layer + 2 * 4096 * 32768 + 4096
    assert counts.gqa_param_count(MISTRAL) == total
    assert 3.7e9 < total < 3.8e9
    # one decode token at 1,000 attended positions
    mat = 2 * (4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336)
    att = 2 * 2 * 32 * 128 * 1000
    want = 16 * (mat + att) + 2 * 4096 * 32768
    assert counts.gqa_forward_flops_per_token(MISTRAL, 1000, lm_head=True) == want
    assert counts.gqa_forward_flops_per_token(MISTRAL, 1000, lm_head=False) == 16 * (mat + att)


def test_one_paged_attention_call_by_hand():
    # two live rows at 1,000 and 3,000 positions, one free slot, one layer
    c = counts.paged_attention_call(MISTRAL, [1000, 3000, 0], sq=1)
    kv_bytes = 2 * 8 * 128 * 2 * 4000            # K and V, 8 heads x 128, bf16
    qo_bytes = 2 * 2 * 1 * 32 * 128 * 2          # q in and o out, two rows
    assert c["bytes"] == kv_bytes + qo_bytes == 16_384_000 + 32_768
    assert c["flops"] == 2 * 2 * 32 * 128 * 4000
    t, binds = counts.roofline_seconds(c["flops"], c["bytes"], peaks.peaks_for("TPU v5 lite"))
    assert binds == "bytes" and t == pytest.approx(c["bytes"] / 819e9)
    # a token of KV costs 64 KiB over the 16 layers
    assert 16 * 2 * 8 * 128 * 2 == 64 * 1024
