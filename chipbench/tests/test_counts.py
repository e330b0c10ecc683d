"""The benchmark's operation and byte counts against hand counts."""
import pytest

from chipbench import counts, harness

#: StableLM-2-1.6B cut to three layers, the payload the program's smoke
#: test ran on the chip: the sizes the counts were first checked at
STABLELM_L3 = {"hidden_size": 2048, "intermediate_size": 5632,
               "num_attention_heads": 32, "num_key_value_heads": 32,
               "num_hidden_layers": 3, "vocab_size": 100352,
               "tie_word_embeddings": True}


@pytest.fixture(scope="module")
def smollm2():
    return harness.read_json(harness.HERE / "configs" / "smollm2-1.7b-L3.json")


def test_param_count_matches_the_program_at_three_layers(smollm2):
    # embedding 49152·2048, per layer 4·2048² + 3·2048·8192 + 2·2048,
    # final norm 2048
    per_layer = 4 * 2048 ** 2 + 3 * 2048 * 8192 + 2 * 2048
    assert counts.dense_param_count(smollm2) == 49152 * 2048 + 3 * per_layer + 2048
    assert counts.dense_param_count(smollm2) == 302_004_224
    # the program's decoder, built from the same file, holds as many
    import jax
    from chipbench.drivers.deploy_round import model_config
    from repro.models.transformer import init_params
    shapes = jax.eval_shape(lambda k: init_params(k, model_config(smollm2)),
                            jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == 302_004_224


def test_flops_per_token_and_per_round_at_the_smoke_sizes():
    assert counts.dense_param_count(STABLELM_L3) == 359_675_904
    per_token = counts.train_flops_per_token(STABLELM_L3, 1024)
    assert per_token == 6 * 359_675_904 + 12 * 3 * 1024 * 2048
    assert round(per_token / 1e9, 2) == 2.23
    # 2 agents × 2 × 1024 tokens × 2 epochs = 8192 token-passes: 18.3 TFLOP
    flops = counts.round_train_flops(STABLELM_L3, 2, 2, 1024, 2)
    assert flops == per_token * 8192
    assert round(flops / 1e12, 1) == 18.3


def test_untied_head_counts_twice(smollm2):
    untied = dict(smollm2, tie_word_embeddings=False)
    assert (counts.dense_param_count(untied) - counts.dense_param_count(smollm2)
            == 49152 * 2048)


@pytest.mark.parametrize("levels, bits", [(1, 1), (10, 4), (15, 4), (16, 5),
                                          (255, 8), (256, 9)])
def test_wire_bits(levels, bits):
    assert counts.wire_bits(levels) == bits


def test_quant_pipeline_bytes_by_hand():
    # a leaf of 3 whole tiles in bf16 at 8 bits: read 2·3·32768·2 bytes,
    # write the cache 3·32768·2 and the words 3·32768 bytes
    assert counts.quant_pipeline_bytes([3 * 32768], 2, 255) == \
        3 * 32768 * (2 + 2 + 2 + 1)
    # a partial tile is moved whole; a leaf under one tile not at all
    assert counts.quant_pipeline_bytes([32769, 100], 4, 10) == \
        2 * 32768 * (4 + 4 + 4) + 2 * 32768 * 4 // 8


def test_quant_pipeline_bytes_of_the_local_cell(smollm2):
    sizes = counts.dense_leaf_sizes(smollm2, 2)
    assert sum(sizes) == 2 * 302_004_224
    # the norms stay out: 3·2048-value leaves are under one tile
    fused = 2 * (302_004_224 - 2 * 3 * 2048 - 2048)
    assert counts.quant_pipeline_bytes(sizes, 2, 255) == fused * 7
