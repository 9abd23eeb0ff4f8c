"""Serving example: batched prefill + per-token SwiftKV decode (the paper's
workload), comparing the decode-attention impls and the incremental-RoPE
(Eq. 11) decode state against direct recomputation.

Run:  PYTHONPATH=src python examples/serve_decode.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models.api import build_model
from repro.serving import ServingEngine


def main():
    cfg = get_config("gemma-2b", reduced=True)
    batch, prompt_len, gen = 4, 16, 32
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (batch, prompt_len), 0, cfg.vocab_size)

    # the Pallas kernel is compiled for the TPU; on other backends it would
    # need the interpreter, which decode_impl does not ask for
    impls = ["blockwise", "tokenwise", "naive"]
    if jax.default_backend() == "tpu":
        impls.insert(2, "kernel")
    outs = {}
    for impl in impls:
        model = build_model(cfg.replace(decode_impl=impl))
        params = model.init_params(jax.random.PRNGKey(0))
        eng = ServingEngine(model, params, max_len=64, batch=batch)
        _ = eng.generate(prompts, steps=2)        # compile
        t0 = time.perf_counter()
        outs[impl] = np.asarray(eng.generate(prompts, steps=gen))
        dt = time.perf_counter() - t0
        print(f"decode_impl={impl:10s} {batch * gen / dt:8.1f} tok/s")

    for impl in impls[1:]:
        same = np.array_equal(outs["blockwise"], outs[impl])
        print(f"greedy tokens blockwise == {impl}: {same}")
        assert same, (impl, outs["blockwise"][:, :8], outs[impl][:, :8])

    # incremental vs direct RoPE decode state
    for mode in ("incremental", "direct"):
        model = build_model(cfg.replace(rope_mode=mode))
        params = model.init_params(jax.random.PRNGKey(0))
        eng = ServingEngine(model, params, max_len=64, batch=batch)
        outs[mode] = np.asarray(eng.generate(prompts, steps=gen))
    print("greedy tokens incremental-RoPE == direct-RoPE:",
          np.array_equal(outs["incremental"], outs["direct"]))


if __name__ == "__main__":
    main()
