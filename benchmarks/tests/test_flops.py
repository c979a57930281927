from benchmarks import flops, manifest


def test_bert_base_l512_against_a_hand_count(bench_with_waiting_cells):
    cell = manifest.resolve_cell(
        bench_with_waiting_cells, "bert-base-uncased.train-l512"
    )
    # by hand, one sequence of 512 tokens, one layer, multiply-add = 2:
    qkv = 2 * 512 * 768 * 2304            # 1,811,939,328
    scores = 2 * 512 * 512 * 768          #   402,653,184
    context = 2 * 512 * 512 * 768         #   402,653,184
    out = 2 * 512 * 768 * 768             #   603,979,776
    mlp = 2 * (2 * 512 * 768 * 3072)      # 4,831,838,208
    layer = qkv + scores + context + out + mlp
    assert layer == 8_053_063_680
    forward = 12 * layer + 2 * 768 * 2
    assert flops.bert_forward_flops_per_example(cell.config, 512) == forward
    assert flops.bert_train_flops_per_example(cell.config, 512) == 3 * forward
    # ~0.29 TFLOP an example: 300 examples/s on a 197 TFLOP/s chip = 44%
    assert 0.43 < 3 * forward * 300 / 197e12 < 0.45
    assert flops.TRAIN_FLOPS["bert"](cell.config, cell.traffic) == 3 * forward


def test_deepfm_counts_the_tower_and_the_fm_terms():
    cell = manifest.resolve_cell(
        manifest.load_manifest(), "deepfm-criteo-kaggle.train-stream"
    )
    tower = 2 * (429 * 400 + 400 * 400 + 400 * 400 + 400 * 1)
    assert flops.deepfm_forward_flops_per_example(cell.config) == (
        tower + 4 * 26 * 16 + 2 * 13
    )
