"""Generation throughput: KV-cached sampler vs the re-forward oracle.

The serving-path regression gate (companion of bench_attention.py):
naive decoding re-forwards the whole growing context per token —
O(T²) matmuls per token plus a host round trip per step — while
nn/sampling.py runs prefill + ONE lax.scan with per-token
single-position work. Prints one JSON line per config; exits non-zero
if the cached path is not faster at the largest config (its reason to
exist).

Run: python scripts/bench_generation.py [--device auto]
"""
import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "models"))


def time_once(fn):
    t0 = time.time()
    out = fn()
    return out, time.time() - t0


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--device", default="tpu",
                   help="strict: that platform or an error (a rate "
                        "from a host is not a chip number)")
    p.add_argument("--n-new", type=int, default=96)
    args = p.parse_args(argv)

    import importlib
    import veles_tpu as vt
    from veles_tpu import prng
    from veles_tpu.nn import sampling
    lm = importlib.import_module("char_lm")

    results = []
    fail = False
    for n_blocks, dim, prompt_len in ((2, 64, 24), (4, 128, 24)):
        prng.seed_all(7)
        # the speculative A/B (big config only) needs a trained
        # target for a meaningful draft-acceptance rate
        wf = lm.build_workflow(epochs=6 if n_blocks >= 4 else 1,
                               minibatch_size=64,
                               n_blocks=n_blocks, dim=dim,
                               n_train=256, n_valid=64)
        wf.initialize(device=vt.Device_for(args.device))
        wf.run()
        import numpy
        rng = numpy.random.RandomState(3)
        prompt = list(lm.make_corpus(rng, prompt_len))

        # warmup both (compile)
        cached_out = sampling.generate(wf, prompt, args.n_new,
                                       temperature=0)
        naive_out = lm.generate_naive(wf, prompt, args.n_new,
                                      temperature=0)
        assert cached_out == naive_out, "parity broke"
        _, t_cached = time_once(lambda: sampling.generate(
            wf, prompt, args.n_new, temperature=0))
        _, t_naive = time_once(lambda: lm.generate_naive(
            wf, prompt, args.n_new, temperature=0))
        row = {
            "n_blocks": n_blocks, "dim": dim,
            "prompt": prompt_len, "n_new": args.n_new,
            "cached_tok_s": round(args.n_new / t_cached, 1),
            "naive_tok_s": round(args.n_new / t_naive, 1),
            "speedup": round(t_naive / t_cached, 2),
            "platform": wf.device.platform,
        }
        if n_blocks >= 4:
            # speculative decoding over the big target: a 1-block
            # draft of the same vocab proposes gamma tokens per
            # big-model dispatch (nn/speculative.py); exact-greedy
            # equivalence is asserted, speed recorded
            from veles_tpu.nn.speculative import generate_speculative
            prng.seed_all(11)
            draft = lm.build_workflow(epochs=6, minibatch_size=64,
                                      n_blocks=1, dim=dim // 2,
                                      n_train=256, n_valid=64)
            draft.initialize(device=vt.Device_for(args.device))
            draft.run()
            spec_out, stats = generate_speculative(
                wf, draft, prompt, args.n_new, gamma=4)   # warmup
            assert spec_out == cached_out, "speculative parity broke"
            (_, stats), t_spec = time_once(lambda: generate_speculative(
                wf, draft, prompt, args.n_new, gamma=4))
            row["spec_tok_s"] = round(args.n_new / t_spec, 1)
            row["spec_vs_cached"] = round(t_cached / t_spec, 2)
            row["spec_acceptance"] = round(stats["acceptance"], 3)
        results.append(row)
        print(json.dumps(row))
    # the gate: cached must win at the largest config
    if results[-1]["speedup"] < 1.0:
        print("FAIL: cached generation slower than naive", file=sys.stderr)
        fail = True
    return 1 if fail else 0


if __name__ == "__main__":
    sys.exit(main())
