"""The `topk_moe` architecture module gives, bit for bit, what the harness
gave before the block moved into it: the same weights from the same seed,
the same reference logits (float32 and the fp8 control), and the same
operation and byte counts. The constants were recorded with XLA:CPU from
the harness as it was before the move (benchlib.reference and
benchlib.flops), on the inputs below."""
import hashlib
import json

import jax
import numpy as np
import pytest

import run as bench_run
import tiny

ROWS = (("decode", 9, 1, 5), ("prefill", 0, 4, 4), ("prefill", 2, 3, 10),
        ("prefill", 960, 64, 1500), ("decode", 3000, 1, 1200))
NAMES = ("mixtral-8x7b-l4", "qwen3-235b-a22b-l1")

ATTN = {"['embed']": "e4df8dc635932d0f", "['final_norm']": "e72710531b01d91e",
        "['layers']['attn_norm']": "1ede9ebfa1ad011b",
        "['layers']['mlp_norm']": "1ede9ebfa1ad011b",
        "['layers']['router']": "412e648d8137d8a0",
        "['layers']['wk']": "172c48c75714a7e2",
        "['layers']['wo']": "431c0ed3e82928ff",
        "['layers']['wq']": "679fb09c7a0ea95a",
        "['layers']['wv']": "054bbb359b3a63c5",
        "['lm_head']": "c7a8467224c1f36b"}
WEIGHTS = {
    "mixtral-8x7b-l4": {**ATTN, "['layers']['w13']": "a6ca78a7393bda5d",
                        "['layers']['w2']": "1fa4ad4b839a8466"},
    "qwen3-235b-a22b-l1": {**ATTN, "['layers']['w13']": "9b5ae1010a648d32",
                           "['layers']['w2']": "accf8c2b9f259f02",
                           "['layers']['k_norm']": "26ab507cf4bbb401",
                           "['layers']['q_norm']": "26ab507cf4bbb401"},
}
# (best, picked, arg) at each position of OUT, per quantisation
LOGITS = {
    ("mixtral-8x7b-l4", None): (
        [2.8671064376831055, 3.2791569232940674, 3.3847179412841797,
         2.868751049041748, 2.8121249675750732, 3.0047831535339355,
         3.0534119606018066],
        [0.11040674149990082, 0.5086745619773865, 0.5713570713996887,
         0.783004879951477, -1.7642279863357544, -0.08454561978578568,
         1.9084749221801758],
        [343, 395, 161, 171, 359, 401, 375]),
    ("mixtral-8x7b-l4", "fp8"): (
        [2.7860755920410156, 3.2334721088409424, 3.281200647354126,
         2.9213922023773193, 2.829821825027466, 3.0484158992767334,
         3.075791358947754],
        [0.12190082669258118, 0.398436963558197, 0.5541473627090454,
         0.7712603807449341, -1.7273682355880737, -0.08736611902713776,
         1.8539958000183105],
        [343, 395, 161, 171, 359, 401, 375]),
    ("qwen3-235b-a22b-l1", None): (
        [2.7953004837036133, 2.931173324584961, 3.893629312515259,
         3.209591865539551, 3.346865177154541, 3.1114420890808105,
         2.6025917530059814],
        [0.021535029634833336, 1.0746859312057495, -0.09151944518089294,
         0.6773504614830017, -2.035618782043457, -1.1727135181427002,
         0.13215604424476624],
        [343, 395, 161, 171, 99, 508, 102]),
    ("qwen3-235b-a22b-l1", "fp8"): (
        [2.815377950668335, 2.9856908321380615, 3.8368000984191895,
         3.2099878787994385, 3.3200509548187256, 3.1401748657226562,
         2.5777158737182617],
        [-0.004190345294773579, 1.0923471450805664, -0.04558205232024193,
         0.6411155462265015, -2.0097718238830566, -1.239215612411499,
         0.10943885147571564],
        [343, 395, 161, 171, 99, 508, 102]),
}
OUT = [0, 17, 150, 299, 300, 401, 469]
# forward FLOPs, (FLOPs, bytes) per kernel over ROWS: at the tiny cut and
# at the configuration file's own widths
WORK = {
    ("mixtral-8x7b-l4", "tiny"): (
        48773632.0, (10764288.0, 832767.9995531162), (34075136.0, 1072640)),
    ("mixtral-8x7b-l4", "file"): (
        235417698304.0, (205755777024.0, 11334090743.457964),
        (4361617408.0, 71041024)),
    ("qwen3-235b-a22b-l1", "tiny"): (
        52361728.0, (14352384.0, 719872.0), (34075136.0, 1072640)),
    ("qwen3-235b-a22b-l1", "file"): (
        38446858240.0, (22045261824.0, 4803336028.694241),
        (2180808704.0, 10674176)),
}


@pytest.fixture(scope="module")
def arch():
    return bench_run.load_arch("topk_moe")


@pytest.mark.parametrize("name", NAMES)
def test_weights_unmoved(arch, name):
    conf = tiny.conf(name)
    assert conf["arch"] == "topk_moe"
    w = arch.make_weights(conf, 2**31 + 11, jax.devices()[:1])
    got = {jax.tree_util.keystr(p): hashlib.sha256(
        np.asarray(x).view(np.uint8).tobytes()).hexdigest()[:16]
        for p, x in jax.tree_util.tree_leaves_with_path(w)}
    assert got == WEIGHTS[name]


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("quant", [None, "fp8"])
def test_reference_logits_unmoved(arch, name, quant):
    conf = tiny.conf(name)
    w = arch.make_weights(conf, 2**31 + 11, jax.devices()[:1])
    rng = np.random.default_rng(5)
    toks = rng.integers(1, conf["vocab_size"], 512, dtype=np.int32)
    seg = np.full(512, -1, np.int32)
    seg[:300], seg[300:470] = 0, 1
    want = rng.integers(0, conf["vocab_size"], len(OUT), dtype=np.int32)
    xo = arch.hidden(conf, w, toks, seg, np.asarray(OUT, np.int32),
                     quant=quant)
    best, picked, top = arch.head(conf, w, xo, want, quant=quant)
    eb, ep, ea = LOGITS[name, quant]
    np.testing.assert_array_equal(best, np.float32(eb))
    np.testing.assert_array_equal(picked, np.float32(ep))
    np.testing.assert_array_equal(top, ea)


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("cut", ["tiny", "file"])
def test_work_counts_unmoved(arch, name, cut):
    conf = (tiny.conf(name) if cut == "tiny" else json.loads(
        (tiny.BENCH / "configs" / f"{name}.json").read_text()))
    m = arch.dims(conf)
    fwd, gemm, attn = WORK[name, cut]
    assert arch.forward(m, ROWS) == fwd
    assert arch.costs["moe_grouped_matmul"](m, ROWS) == gemm
    assert arch.costs["paged_attention"](m, ROWS) == attn
