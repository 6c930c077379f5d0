"""The architecture modules of ``bench/yardstick/arch`` and the dispatch to
them, on the CPU.

Parity: the values below were computed by the yardstick before it read
each block from its configuration file (one fixed dense decoder), and are
frozen here, not recomputed.  The dense module has to give the same
weights bit for bit, the same reference gaps and the same modelled work,
so the ``qwen2.5-3b`` cells read what they read before.

The published StarCoder2 block (LayerNorm with a bias, a bias on every
linear) is checked against a plain float64 forward pass written here, one
sequence at a time; leaving out any one of its biases breaks the
agreement.  The program has no LayerNorm yet: the harness refuses a
program that departs from the file's block, by the fields the
architecture module names.  Its six biases the program serves already,
on one device (``test_bench_reference``) and tensor-parallel on a
``(1, 4)`` mesh of CPU devices, where a row-parallel bias added on every
shard, or left out, is caught.
"""

import dataclasses
import hashlib
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

import _tiny
from bench import harness
from bench.yardstick import arch, weights
from bench.yardstick.arch import dense

# --------------------------------------------------------------- parity
WEIGHTS_SHA256 = {
    "float32": "15fe0aadf95486915ac51824f4073f0934bb7161aed0b30c382e886d0a452088",
    "bfloat16": "29dbb1200d6670fc4badc90d7761996780dec167a87f7e8e8a2928de6b7d7a86",
}
GAPS = [0.4704555571079254, 0.7143787741661072, 0.7150062918663025,
        0.6370668411254883, 0.38378915190696716, 0.8060016632080078,
        0.46967044472694397, 0.5281307101249695, 0.8794445991516113,
        0.5417127013206482, 0.6725277900695801, 0.5887191295623779,
        0.5052655935287476, 0.6398224234580994, 0.5982606410980225,
        0.6965018510818481, 0.7793555855751038, 0.43728411197662354,
        0.4636101722717285, 0.5589969754219055, 0.681894063949585]
DECODE_CONTEXTS = [300, 1, 4096, 1500]
PREFILL_LANES = [(0, 128), (1000, 128), (4000, 17)]
COSTS = {
    # the published qwen2.5-3b and the tiny cell of _tiny
    "full": {"weight_bytes": 6171877376.0,
             "decode_step": (26459243968.0, 6411532032.0),
             "prefill_step": (1581069881280.0, 7803517088.0),
             "attn_decode": (1762872768.0, 218573568.0),
             "attn_prefill": (63574925760.0, 274900896.0)},
    "tiny": {"weight_bytes": 1345792.0,
             "decode_step": (6052728.0, 4488320.0),
             "prefill_step": (163627864.0, 8008784.0),
             "attn_decode": (3349496.0, 3023744.0),
             "attn_prefill": (120793720.0, 2979664.0)},
}


def _config(name):
    with open(os.path.join(_tiny.ROOT, "bench", "configs",
                           f"{name}.json")) as f:
        return json.load(f)


def _tree_sha256(tree):
    h = hashlib.sha256()
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, a in sorted(flat, key=lambda t: jax.tree_util.keystr(t[0])):
        a = np.asarray(a)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_architecture_is_found_by_file_name():
    cfg = _config("qwen2.5-3b")
    assert "yardstick" not in cfg
    assert arch.of(cfg) is dense
    assert arch.of(_config("starcoder2-15b-tp4")) is dense
    assert _tiny.cell().arch is dense
    with pytest.raises(ValueError):
        arch.load("../harness")
    with pytest.raises(ModuleNotFoundError):
        arch.of({"yardstick": "no_such_block"})


@pytest.mark.parametrize("dtype", sorted(WEIGHTS_SHA256))
def test_qwen_weights_unchanged(dtype):
    d = _tiny.cell(dtype=dtype).dims
    tree = jax.jit(lambda k: dense.params(d, k))(weights.seed_key(2 ** 33 + 7))
    assert _tree_sha256(tree) == WEIGHTS_SHA256[dtype]


def test_qwen_reference_gaps_unchanged():
    d = _tiny.cell().dims
    rng = np.random.default_rng(11)
    seqs = [(rng.integers(0, d.vocab, n).tolist(),
             rng.integers(0, d.vocab, m).tolist())
            for n, m in ((40, 6), (9, 12), (23, 3))]
    gaps = dense.served_gaps(d, 2 ** 31 + 77, seqs, tokens=128, rows=32,
                             q_block=64)
    # float32 at Precision.HIGHEST: equal to a few units in the last place
    np.testing.assert_allclose(gaps, GAPS, rtol=1e-6, atol=0)


@pytest.mark.parametrize("size", sorted(COSTS))
def test_qwen_costs_unchanged(size):
    d = (dense.dims(_config("qwen2.5-3b")) if size == "full"
         else _tiny.cell().dims)
    want = COSTS[size]
    assert dense.weight_bytes(d) == want["weight_bytes"]
    assert dense.decode_step(d, DECODE_CONTEXTS, 128) == want["decode_step"]
    assert dense.attn_decode(d, DECODE_CONTEXTS, 128) == want["attn_decode"]
    assert dense.prefill_step(d, PREFILL_LANES, 128) == want["prefill_step"]
    assert dense.attn_prefill(d, PREFILL_LANES, 128) == want["attn_prefill"]


# ------------------------------------------------- published StarCoder2
def test_starcoder2_file_states_the_published_block():
    cfg = _config("starcoder2-15b-tp4")
    d = dense.dims(cfg)
    assert (d.norm, d.norm_eps, d.qkv_bias, d.out_bias, d.window,
            d.gated, d.tied) == ("layer_norm", 1e-5, True, True, 4096,
                                 False, False)
    assert cfg["reduced"] == [] and "published" not in cfg
    assert cfg["program"]["overrides"] == {
        "sliding_window": 4096, "qkv_bias": True, "norm_eps": 1e-5}


def _plain_logits(d, p, seq, drop=()):
    """Logits at every position of one sequence, in float64, position by
    position, from the published equations; ``drop`` names bias leaves
    to leave out."""
    f = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), p)

    def bias(tree, name, path):
        return 0.0 if path in drop else tree[name]

    def norm(x, w, b):
        c = x - x.mean(-1, keepdims=True)
        return c / np.sqrt((c * c).mean(-1, keepdims=True) + d.norm_eps) \
            * (1.0 + w) + b

    def lin(q, x, path):
        return x @ q["w"] + bias(q, "bias", path)

    def rope(x, t):
        inv = 1.0 / d.rope_theta ** (np.arange(0, d.head_dim, 2)
                                     / d.head_dim)
        a = t * inv
        x1, x2 = x[..., :d.head_dim // 2], x[..., d.head_dim // 2:]
        return np.concatenate([x1 * np.cos(a) - x2 * np.sin(a),
                               x2 * np.cos(a) + x1 * np.sin(a)], -1)

    n, g = len(seq), d.n_heads // d.n_kv_heads
    x = f["embed"][np.asarray(seq)]
    for i in range(d.n_layers):
        lp = jax.tree_util.tree_map(lambda a: a[i], f["layers"])
        h = norm(x, lp["ln1"], bias(lp, "ln1_bias", "ln1_bias"))
        q = lin(lp["attn"]["wq"], h, "wq").reshape(n, d.n_heads, -1)
        k = lin(lp["attn"]["wk"], h, "wk").reshape(n, d.n_kv_heads, -1)
        v = lin(lp["attn"]["wv"], h, "wv").reshape(n, d.n_kv_heads, -1)
        o = np.zeros((n, d.n_heads, d.head_dim))
        for t in range(n):
            keys = [s for s in range(t + 1)
                    if not d.window or s > t - d.window]
            for hq in range(d.n_heads):
                qt = rope(q[t, hq], t)
                kk = np.stack([rope(k[s, hq // g], s) for s in keys])
                sc = kk @ qt / np.sqrt(d.head_dim)
                w = np.exp(sc - sc.max())
                o[t, hq] = (w / w.sum()) @ v[keys, hq // g]
        x = x + lin(lp["attn"]["wo"], o.reshape(n, -1), "wo")
        h = norm(x, lp["ln2"], bias(lp, "ln2_bias", "ln2_bias"))
        u = lin(lp["mlp"]["w_up"], h, "w_up")
        a = 0.5 * u * (1 + np.tanh(np.sqrt(2 / np.pi)
                                   * (u + 0.044715 * u ** 3)))
        x = x + lin(lp["mlp"]["w_down"], a, "w_down")
    h = norm(x, f["final_norm"],
             bias(f, "final_norm_bias", "final_norm_bias"))
    return h @ f["lm_head"]["w"]


def _published_case():
    d = _tiny.cell("starcoder2-15b-tp4", window=24).dims
    seed = 2 ** 32 + 19
    p = jax.jit(lambda k: dense.params(d, k))(weights.seed_key(seed))
    rng = np.random.default_rng(19)
    # one sequence longer than the window, one shorter, packed together
    seqs = [rng.integers(0, d.vocab, n).tolist() for n in (40, 13)]
    pairs = [(s[:5], s[5:]) for s in seqs]
    gaps = dense.served_gaps(d, seed, pairs, tokens=64, rows=64, q_block=64)
    return d, p, seqs, gaps


def _plain_gaps(d, p, seqs, drop=()):
    out = []
    for s in seqs:
        logits = _plain_logits(d, p, s[:-1], drop)[4:]
        out.extend(logits.max(-1) - logits[np.arange(len(logits)), s[5:]])
    return np.asarray(out)


# float32 at Precision.HIGHEST against float64, logits of order 1
PLAIN_ATOL = 1e-4


def test_published_starcoder2_block_matches_a_plain_forward():
    d, p, seqs, gaps = _published_case()
    assert d.norm == "layer_norm" and d.out_bias and d.window == 24
    assert gaps.shape == (35 + 8,)
    np.testing.assert_allclose(gaps, _plain_gaps(d, p, seqs),
                               atol=PLAIN_ATOL, rtol=0)


@pytest.mark.parametrize("leaf", ["wo", "w_up", "w_down", "wq", "ln1_bias",
                                  "ln2_bias", "final_norm_bias"])
def test_each_published_bias_is_applied(leaf):
    d, p, seqs, gaps = _published_case()
    off = np.abs(gaps - _plain_gaps(d, p, seqs, drop=(leaf,))).max()
    assert off > 100 * PLAIN_ATOL, (leaf, off)


def test_weight_bytes_count_every_published_leaf():
    d = _tiny.cell("starcoder2-15b-tp4", dtype="bfloat16").dims
    shapes = jax.eval_shape(lambda k: dense.params(d, k),
                            jax.ShapeDtypeStruct((2,), np.uint32))
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    names = {jax.tree_util.keystr(k) for k, _ in leaves}
    for want in ("['layers']['attn']['wo']['bias']",
                 "['layers']['mlp']['w_up']['bias']",
                 "['layers']['mlp']['w_down']['bias']",
                 "['layers']['ln1_bias']", "['final_norm_bias']"):
        assert want in names
    total = sum(a.size * a.dtype.itemsize for _, a in leaves)
    embed = d.vocab * d.d_model * d.itemsize      # untied: gathered only
    assert dense.weight_bytes(d) == total - embed


def _program(**block):
    """A stand-in for the program's ``ModelConfig`` at tiny StarCoder2
    widths with q/k/v biases and epsilon 1e-5, and with the fields
    ``block`` adds (a program without them serves RMSNorm and no o or MLP
    bias)."""
    from repro.config import get_arch
    from repro.config.base import ModelConfig

    fields = [(k, type(v), v) for k, v in block.items()]
    cls = dataclasses.make_dataclass("Program", fields, bases=(ModelConfig,),
                                     frozen=True)
    base = dataclasses.replace(get_arch("starcoder2-15b"),
                               **_tiny.PROGRAM, dtype="float32",
                               sliding_window=4096, norm_eps=1e-5,
                               qkv_bias=True)
    return cls(**dataclasses.asdict(base))


def test_program_check_names_the_published_block(monkeypatch):
    import repro.config

    c = _tiny.cell("starcoder2-15b-tp4")
    c.cfg["sliding_window"] = 4096
    c.cfg["program"]["overrides"] = {}
    ok = _program(norm_type="layer_norm", use_bias=True)
    rms = _program()
    assert all(a == b for a, b in dense.program_fields(ok, c.dims).values())
    monkeypatch.setattr(repro.config, "get_arch", lambda name: ok)
    assert harness.program_config(c) == ok

    bad = {k for k, (a, b) in dense.program_fields(rms, c.dims).items()
           if a != b}
    assert bad == {"norm_type"}
    monkeypatch.setattr(repro.config, "get_arch", lambda name: rms)
    with pytest.raises(SystemExit, match="norm_type"):
        harness.program_config(c)


def test_six_biases_served_once_on_a_mesh():
    """StarCoder2 with the program's norm, its six biases and a window
    shorter than the prompts, served by ``ServeEngine`` on a
    ``(data=1, model=4)`` mesh of CPU devices: the check passes.  The
    o and ``c_proj`` biases are row-parallel (replicated beside weights
    split over ``model``); the same run with the program's o bias added
    on each of the four shards, or its ``c_proj`` bias left out, fails."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import sys, time
        sys.path[:0] = ["bench/tests", ".", "src"]
        import _tiny
        from bench import harness

        harness.peaks = lambda kind: _tiny.CPU_PEAK
        make = harness.make_params

        def planted(fault):
            def make_params(cell, seed, mesh):
                p = make(cell, seed, mesh)
                m = p["layers"]["attn"]["wo"]
                if fault == "o bias on every shard":
                    m["bias"] = m["bias"] * 4
                elif fault == "c_proj bias left out":
                    b = p["layers"]["mlp"]["w_down"]
                    b["bias"] = b["bias"] * 0
                return p
            return make_params

        for fault in ("none", "o bias on every shard",
                      "c_proj bias left out"):
            c = _tiny.cell("starcoder2-15b-tp4", window=24,
                           norm=_tiny.PROGRAM_NORM)
            c.chips, c.cfg["mesh"] = 4, [1, 4]
            harness.make_params = planted(fault)
            out = harness.run(c, 2 ** 31 + 41, 1.0, False,
                              time.perf_counter(), harness.CompileCounter(),
                              log=lambda m: None)
            g = out["checks"]["mean_gap"]
            print("RESULT", fault, out["correct"], g["value"], g["limit"],
                  out["device"]["count"], flush=True)
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=_tiny.ROOT, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    got = {}
    for line in out.stdout.splitlines():
        if line.startswith("RESULT "):
            *fault, correct, value, limit, count = line.split()[1:]
            got[" ".join(fault)] = (correct == "True", float(value),
                                    float(limit), int(count))
    assert set(got) == {"none", "o bias on every shard",
                        "c_proj bias left out"}, out.stdout
    assert got["none"][0] and got["none"][3] == 4, got
    for fault in ("o bias on every shard", "c_proj bias left out"):
        correct, value, limit, _ = got[fault]
        assert not correct and value > limit, (fault, got)
