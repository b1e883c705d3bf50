"""Character language model — next-token prediction zoo member.

New capability vs the reference (no language modeling anywhere in 2015
VELES): embedding → RoPE transformer stack → LM head, trained with
``loss_function="softmax_seq"`` (per-token cross-entropy on shifted
targets). The corpus is generated from a small deterministic grammar,
so the next-token structure is real and in-image (anchor like
models/lines.py); swap ``make_corpus`` for a file to train on text.

Identical-block stacks pipeline over ``--mesh pipeline=N`` and the
sequence axis shards over ``--mesh sequence=N`` unchanged.

Run: python models/char_lm.py [--epochs N]
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy  # noqa: E402

import veles_tpu as vt  # noqa: E402
from veles_tpu import nn  # noqa: E402
from veles_tpu.loader import FullBatchLoaderMSE  # noqa: E402

SEQ_LEN = 32
VOCAB = 16


def make_corpus(rng, n_chars):
    """Markov-ish grammar: each symbol strongly prefers (s + 1) % 8 or
    a jump into the 8-15 'punctuation' range that returns to 0."""
    out = numpy.empty(n_chars, dtype=numpy.int32)
    s = 0
    for i in range(n_chars):
        out[i] = s
        r = rng.rand()
        if s < 8:
            s = (s + 1) % 8 if r < 0.8 else 8 + rng.randint(0, 8)
        else:
            s = 0 if r < 0.9 else 8 + rng.randint(0, 8)
    return out


class CharLMLoader(FullBatchLoaderMSE):
    hide_from_registry = True

    def __init__(self, workflow, n_train=1536, n_valid=256, **kwargs):
        super().__init__(workflow, **kwargs)
        self.n_train, self.n_valid = n_train, n_valid

    def load_data(self):
        rng = numpy.random.RandomState(41)
        n = self.n_valid + self.n_train
        corpus = make_corpus(rng, n * SEQ_LEN + 1)
        x = corpus[:-1].reshape(n, SEQ_LEN)
        y = corpus[1:].reshape(n, SEQ_LEN)       # next-token targets
        self.create_originals(x, None, targets=y)
        self.class_lengths = [0, self.n_valid, self.n_train]


def build_workflow(epochs=10, minibatch_size=64, lr=0.003, n_blocks=2,
                   dim=32, n_train=1536, n_valid=256, text_file=None,
                   seq_len=SEQ_LEN, arch="transformer"):
    """``text_file``: train on a real text file via TextFileLoader
    (vocab sized to the corpus) instead of the generated grammar.
    ``arch``: "transformer" (RoPE blocks), "lstm" (stacked
    return-sequences LSTMs) or "ssm" (gated linear-attention SSD
    blocks) — the recurrent families ride the same LM surface, so they
    get the same real-data quality gate AND the O(1)-state serving
    lane end-to-end."""
    if text_file:
        from veles_tpu.loader import TextFileLoader
        # one cheap scan for the vocabulary (embedding/head sizes need
        # it BEFORE the loader's load_data runs at initialize); passing
        # it back in pins the loader to the same table
        with open(text_file, "r", encoding="utf-8",
                  errors="replace") as f:
            chars = "".join(sorted(set(f.read())))
        loader = TextFileLoader(None, files=[text_file],
                                seq_len=seq_len, vocab=chars,
                                minibatch_size=minibatch_size,
                                name="chars")
        # vocab_size includes the loader's reserved unk slot — sizing
        # the embedding/head from len(chars) would put unk out of range
        vocab = loader.vocab_size
    else:
        loader = CharLMLoader(None, n_train=n_train, n_valid=n_valid,
                              minibatch_size=minibatch_size,
                              name="chars")
        vocab = VOCAB
    if arch not in ("transformer", "lstm", "ssm"):
        raise ValueError("arch must be 'transformer', 'lstm' or "
                         "'ssm', got %r" % (arch,))
    if arch == "lstm":
        body = [{"type": "lstm", "hidden_size": dim,
                 "return_sequences": True, "solver": "adam",
                 "learning_rate": lr, "name": "lstm%d" % i}
                for i in range(n_blocks)]
    elif arch == "ssm":
        body = [{"type": "ssm_block", "n_heads": 4, "solver": "adam",
                 "learning_rate": lr, "name": "ssm%d" % i}
                for i in range(n_blocks)]
    else:
        body = [{"type": "transformer_block", "n_heads": 4,
                 "ffn_hidden": 2 * dim, "causal": True, "rope": True,
                 "solver": "adam", "learning_rate": lr,
                 "name": "blk%d" % i} for i in range(n_blocks)]
    layers = ([{"type": "embedding", "vocab_size": vocab, "dim": dim,
                "solver": "adam", "learning_rate": lr}]
              + body
              + [{"type": "lm_head", "vocab_size": vocab,
                  "solver": "adam", "learning_rate": lr}])
    wf = nn.StandardWorkflow(
        name="char-lm", layers=layers, loader_unit=loader,
        loss_function="softmax_seq",
        decision_config=dict(max_epochs=epochs, fail_iterations=50),
    )
    return wf


class SyntheticTokenLoader(FullBatchLoaderMSE):
    """Random token streams at arbitrary (seq_len, vocab) — the LM
    throughput-bench surface (content does not affect throughput, and
    the int32 upload is tiny next to image data)."""

    hide_from_registry = True

    def __init__(self, workflow, seq_len=512, vocab=256, n_train=1024,
                 n_valid=128, **kwargs):
        super().__init__(workflow, **kwargs)
        self.seq_len, self.vocab = seq_len, vocab
        self.n_train, self.n_valid = n_train, n_valid

    def load_data(self):
        rng = numpy.random.RandomState(2027)
        n = self.n_valid + self.n_train
        stream = rng.randint(0, self.vocab, n * self.seq_len + 1,
                             dtype=numpy.int32)
        self.create_originals(stream[:-1].reshape(n, self.seq_len), None,
                              targets=stream[1:].reshape(n, self.seq_len))
        self.class_lengths = [0, self.n_valid, self.n_train]


def build_bench_workflow(seq_len=512, dim=512, n_blocks=6,
                         ffn_hidden=2048, n_heads=8, vocab=256,
                         minibatch_size=16, n_train=1024, n_valid=128,
                         lr=1e-4, epochs_per_dispatch=1):
    """GPT-style stack at throughput-bench scale (the modern-workload
    counterpart of the AE bench): token embedding → N pre-LN RoPE
    blocks → LM head, per-token CE. Sized so the matmuls dominate
    dispatch latency (~19M matmul params at the defaults)."""
    loader = SyntheticTokenLoader(
        None, seq_len=seq_len, vocab=vocab, n_train=n_train,
        n_valid=n_valid, minibatch_size=minibatch_size, name="lm-bench")
    layers = ([{"type": "embedding", "vocab_size": vocab, "dim": dim,
                "solver": "adam", "learning_rate": lr}]
              + [{"type": "transformer_block", "n_heads": n_heads,
                  "ffn_hidden": ffn_hidden, "causal": True, "rope": True,
                  "solver": "adam", "learning_rate": lr,
                  "name": "blk%d" % i} for i in range(n_blocks)]
              + [{"type": "lm_head", "vocab_size": vocab,
                  "solver": "adam", "learning_rate": lr}])
    return nn.StandardWorkflow(
        name="char-lm-bench", layers=layers, loader_unit=loader,
        loss_function="softmax_seq",
        decision_config=dict(max_epochs=10 ** 9,
                             fail_iterations=10 ** 9),
        steps_per_dispatch=n_train // minibatch_size,
        epochs_per_dispatch=epochs_per_dispatch,
    )


def generate(wf, prompt, n_new, temperature=1.0, seed=0):
    """Sample continuations from the trained causal stack via the
    KV-cached on-device sampler (nn/sampling.py: prefill + one
    lax.scan — a single dispatch end to end)."""
    from veles_tpu.nn import sampling
    return sampling.generate(wf, prompt, n_new, temperature=temperature,
                             seed=seed)


def generate_naive(wf, prompt, n_new, temperature=1.0, seed=0):
    """Reference sampler: re-forward the FULL growing sequence each
    step — O(T^2) per token and one retrace per length; kept as the
    oracle the KV-cached path is tested against
    (tests/test_transformer.py). RoPE has no trained-length cap, so the
    growing context needs no windowing."""
    import jax
    import jax.numpy as jnp
    params = {f.name: {k: v.device_view()
                       for k, v in f.param_arrays().items()}
              for f in wf.forwards if f.PARAMETERIZED}

    @jax.jit
    def logits_fn(tokens):
        x = tokens[None, :]
        for f in wf.forwards:
            x = f.apply(params.get(f.name, {}), x, train=False)
        return x[0, -1]

    key = jax.random.key(seed)
    toks = list(int(t) for t in prompt)
    for _ in range(n_new):
        logits = logits_fn(jnp.asarray(toks, dtype=jnp.int32))
        key, sub = jax.random.split(key)
        if temperature <= 0:
            nxt = int(jnp.argmax(logits))
        else:
            nxt = int(jax.random.categorical(sub, logits / temperature))
        toks.append(nxt)
    return toks[len(prompt):]


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=10)
    p.add_argument("--mb", type=int, default=64)
    p.add_argument("--lr", type=float, default=0.003)
    p.add_argument("--blocks", type=int, default=2)
    p.add_argument("--sample", type=int, default=48,
                   help="tokens to sample after training (0 = skip)")
    p.add_argument("--text", default=None, metavar="FILE",
                   help="train on a real text file (TextFileLoader) "
                        "instead of the generated grammar")
    p.add_argument("--backend", default="auto")
    args = p.parse_args(argv)

    wf = build_workflow(args.epochs, args.mb, args.lr, args.blocks,
                        text_file=args.text)
    wf.initialize(device=vt.Device_for(args.backend))
    t0 = time.time()
    wf.run()
    dt = time.time() - t0
    res = wf.gather_results()
    print("best per-token error: %.4f (epoch %d)" %
          (res["best_err"], res["best_epoch"]))
    print("throughput: %.0f samples/sec" %
          (wf.loader.samples_served / dt))
    if args.sample:
        loader = wf.loader
        if args.text:
            # prompt with text that EXISTS in the corpus vocabulary —
            # encode() maps unknown chars to id 0, which would prompt
            # the model with something other than what we print
            seed_text = loader.decode(
                loader.original_data.mem[0][:8])
            prompt = list(loader.encode(seed_text))
            toks = generate(wf, prompt, args.sample, temperature=0.8)
            print("sample: %r" % loader.decode(prompt + toks))
        else:
            toks = generate(wf, [0, 1, 2], args.sample, temperature=0.8)
            print("sample:", " ".join(str(t) for t in toks))
    return res


if __name__ == "__main__":
    main()
