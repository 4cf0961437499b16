"""The ``stack`` family's operation and byte counts, checked by hand for
one layer of each configuration.

    python -m pytest chipbench/tests -q
"""
import smoke  # noqa: F401
import spec

DS = spec.load_json(spec.HERE / "configs" / "deepseek-coder-33b-8L.json")
counts = spec.family(DS, "counts")
#: a hybrid at the widths of the program's zamba2-2.7b, for the mamba2 and
#: shared-block counts (no cell of the benchmark runs it yet)
ZB = {"hidden_size": 2560, "intermediate_size": 10240,
      "num_attention_heads": 32, "num_key_value_heads": 32, "head_dim": 80,
      "num_hidden_layers": 54, "vocab_size": 32000,
      "tie_word_embeddings": True, "mamba_d_state": 64, "mamba_d_conv": 4,
      "mamba_expand": 2, "mamba_headdim": 64,
      "block_pattern": [{"kind": "mamba2", "mlp": "none"}] * 5
      + [{"kind": "attn", "mlp": "glu", "shared": True}]}
ATTN_GLU = {"kind": "attn", "mlp": "glu"}
MAMBA = {"kind": "mamba2", "mlp": "none"}


def test_deepseek_layer():
    # wq and wo 7168 x 7168, wk and wv 7168 x (8 x 128), three 7168 x 19200
    params = 7168 * 7168 * 2 + 7168 * 1024 * 2 + 3 * 7168 * 19200
    assert params == 530_317_312
    assert counts.layer_matmul_params(DS, ATTN_GLU) == params
    # at 100 positions: 2 x params, and 4 x 56 heads x 128 x 100
    assert counts.layer_flops(DS, ATTN_GLU, 100) == 2 * params + 2_867_200


def test_deepseek_step_bytes():
    # 8 layers of products and two norms, the untied head and final norm
    per_layer = 530_317_312 + 2 * 7168
    assert counts.weight_bytes(DS) == 2 * (8 * per_layer + 7168 * 32256
                                           + 7168)
    assert counts.weight_bytes(DS) == 8_947_742_720
    # keys and values of one position: 8 layers x 2 x 8 heads x 128 x 2 B
    assert counts.kv_bytes_per_position(DS) == 32_768
    assert counts.decode_token_bytes(DS, 10) == 327_680


def test_zamba2_mamba_layer():
    # in_proj 2560 -> 2 x 5120 + 2 x 64 + 80 heads; out_proj 5120 -> 2560
    params = 2560 * 10448 + 5120 * 2560
    assert params == 39_854_080
    assert counts.layer_matmul_params(ZB, MAMBA) == params
    # state update and readout: 6 x 80 heads x 64 x 64; conv: 2 x 4 x 5248
    assert counts.layer_flops(ZB, MAMBA, 999) == (2 * params + 1_966_080
                                                  + 41_984)


def test_zamba2_shared_block_and_state():
    shared = ZB["block_pattern"][-1]
    assert counts.layer_matmul_params(ZB, shared) == (4 * 2560 * 2560
                                                      + 3 * 2560 * 10240)
    # 9 applications of the shared attention x 2 x 32 heads x 80 x 2 B
    assert counts.kv_bytes_per_position(ZB) == 92_160
    # 45 mamba layers x (80 x 64 x 64 float32 + 3 x 5248 bf16 conv tail)
    assert counts.state_bytes_per_sequence(ZB) == 45 * (1_310_720 + 31_488)


def test_prefill_is_causal_and_has_no_head():
    n = 7
    per_token = sum(counts.layer_flops(DS, ATTN_GLU, 0) for _ in range(8))
    attn = 8 * 4 * 56 * 128 * n * (n + 1) / 2
    assert counts.prefill_flops(DS, n) == n * per_token + attn
    assert counts.decode_flops(DS, 1) == (
        8 * counts.layer_flops(DS, ATTN_GLU, 1) + 2 * 7168 * 32256)
