#!/usr/bin/env python3
"""The control of a serving cell's correctness limits: the plain reference,
its activations stored in a lower precision, put where the program stands in
``runners/serve_model.py``'s own comparison.

    JAX_PLATFORMS=cpu python3 benchmark/precision_control.py \\
        --config ouro-2.6b --section serve --seed <n> [--layers <l>]

A limit of the comparison that decides ``correct`` lies between two
readings: the largest the program gives on the chip, and what the reference
gives one precision below the section's, which has to come out NOT correct.
This computes the second, and a witness for the first: for the section's
own dtype and then the nearest below it (``BELOW``), the adapter's
reference with ``round_to`` set (``reference/ouro_block.py`` says what is
rounded) decodes greedily from the check's prompts and is then handed to
``serve_model._check_against_reference`` with the tokens it chose, as the
decoder is with the engine's. The last line is one JSON object, {dtype: the
gates' readings}; the exit code is 0 where the section's own dtype passes
and the one below fails.

The weights are the decoder's own (the runner's recipe at the section's
dtype, from ``--seed``), at the configuration's published widths; only
``--layers`` may cut the depth below the section's, for the benchmark's own
tests (the readings grow with the blocks a token passes, so a limit is
argued from a run at the section's depth). It runs on the CPU: the
reference is float32 under ``highest`` and has no cache, so every token is
a full forward (about 7 minutes for both dtypes at 12 layers on 8 cores).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

BELOW = {"bfloat16": "float8_e4m3fn", "float16": "float8_e4m3fn",
         "float32": "bfloat16"}


class ReferenceAsProgram:
    """The members of ``LlamaDecoder`` the comparison touches, over the
    adapter's reference with ``round_to`` set. There is no cache: what
    stands for it is the ids so far, and every call is a full forward over
    them (kept by its ids, so the comparison's prefill and steps cost
    nothing after ``generate``)."""

    def __init__(self, adapter, params, arch, layers, round_to):
        self.adapter, self.params, self.arch = adapter, params, arch
        self.layers, self.round_to = layers, round_to
        self._seen = {}

    def _last(self, ids: np.ndarray) -> np.ndarray:
        key = ids.tobytes()
        if key not in self._seen:
            self._seen[key] = self.adapter.reference_logits(
                self.params, self.arch, self.layers, ids,
                np.array([ids.shape[1] - 1]), round_to=self.round_to)
        return self._seen[key]

    def _empty_cache(self, batch):
        return None, None

    def _prefill(self, params, ids, kc, vc):
        ids = np.asarray(ids, np.int32)
        return self._last(ids), ids, vc

    def _step(self, params, tok, kc, vc, pos):
        ids = np.concatenate([kc, np.asarray(tok, np.int32)], axis=1)
        return self._last(ids), ids, vc

    def generate(self, prompt: np.ndarray, budget: int) -> np.ndarray:
        """``prompt`` and ``budget`` greedy tokens after it."""
        ids = np.asarray(prompt, np.int32)[None]
        for _ in range(budget):
            tok = np.argmax(self._last(ids)[0]).astype(np.int32)
            ids = np.concatenate([ids, tok[None, None]], axis=1)
        return ids[0]


def readings(config: str, section: str, seed: int, round_to, layers=None,
             prompts=None, budget=None, root: str = HERE) -> dict:
    """{dtype: the comparison's dict} for each dtype of ``round_to``."""
    import paddle_tpu as paddle
    from benchmark.harness import resolve
    from paddle_tpu.inference.generate import LlamaDecoder

    runner = resolve.load_module("runners", "serve_model", root)
    config_file = resolve.load_json("configs", config, root)
    adapter = resolve.load_module("adapters", config_file["adapter"], root)
    arch = adapter.arch_of(config_file)
    sec = dict(config_file["sections"][section])
    if layers is not None:
        sec["num_hidden_layers"] = int(layers)
    cfg = adapter.program_config(arch, sec)
    paddle.seed(seed)
    model = adapter.build_model(cfg)
    model.to(dtype=sec["dtype"])
    params = LlamaDecoder(model, max_len=int(sec["max_len"])).params
    del model
    lens = list(prompts or runner.CHECK_PROMPTS)
    budget = int(budget or runner.CHECK_BUDGET)
    rng = np.random.default_rng([int(seed), 9])     # the runner's stream
    ids = [rng.integers(0, cfg.vocab_size, (n,), dtype=np.int32)
           for n in lens]
    out = {}
    for dt in round_to:
        prog = ReferenceAsProgram(adapter, params, arch,
                                  cfg.num_hidden_layers, dt)
        seqs = [prog.generate(p, budget) for p in ids]
        out[dt] = runner._check_against_reference(
            prog, adapter, arch, cfg.num_hidden_layers, seqs, lens)
        print(f"# {dt}: {json.dumps(out[dt])}", flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--section", default="serve")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--layers", type=int, default=None)
    args = ap.parse_args(argv)
    from benchmark.harness import resolve
    own = resolve.load_json("configs", args.config, HERE)[
        "sections"][args.section]["dtype"]
    out = readings(args.config, args.section, args.seed, [own, BELOW[own]],
                   args.layers)
    print(json.dumps({"config": args.config, "seed": args.seed,
                      "layers": args.layers, "readings": out}))
    return 0 if out[own]["ok"] and not out[BELOW[own]]["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
