"""Block-diffusion training of a mixture-of-experts decoder through
``Trainer.fit``.

A block-diffusion LM (the SDAR recipe: continued training of an
autoregressive checkpoint under a block mask) reads every row twice: its
clean tokens and a noised copy in which each block of ``block_length``
positions has some of them replaced by the mask token. ``noise_rows`` makes
such rows from clean ids in an input pipeline; the registered model
``block_diffusion_lm`` attends them under the block-structured mask (a clean
query sees the clean keys of its own and earlier blocks, a noised query the
clean keys of earlier blocks and the noised keys of its own) and is trained
on the masked positions only. The fused fit returns the model's counters: the
load of the experts held here, the pairs routed, the positions that carried
loss. Here the noise is drawn once with the rows; a job that sees a row more
than once draws it again with another seed.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import numpy as np


def main():
    from sparkflow_tpu.models import build_registry_spec, noise_rows
    from sparkflow_tpu.trainer import Trainer

    smoke = bool(os.environ.get("SPARKFLOW_TPU_SMOKE"))
    vocab, length, block = 512, (64 if smoke else 1024), 4
    mask_token = vocab            # one past the last id: the whole
    # vocabulary is held here, so the mask token takes the next row
    spec = build_registry_spec(
        "block_diffusion_lm", vocab_size=vocab, mask_token_id=mask_token,
        block_length=block, hidden=64 if smoke else 512,
        num_layers=2 if smoke else 4, num_heads=4 if smoke else 8,
        num_kv_heads=2, head_dim=16 if smoke else 64,
        num_experts=8, experts_per_token=2, expert_dim=32 if smoke else 256,
        rope_theta=1e6, max_len=2 * length)

    rows = 8 if smoke else 64
    clean = np.random.default_rng(0).integers(0, vocab, (rows, length))
    noised = noise_rows(clean, block, mask_token, seed=1)   # [rows, 2 L]
    print(f"{rows} rows of {length} tokens fed as {noised.shape[1]} "
          f"positions; {np.mean(noised[:, length:] == mask_token):.1%} of "
          f"the noised copy masked")

    trainer = Trainer(spec, "input_ids", None, optimizer="adam",
                      learning_rate=3e-3, mini_batch_size=2,
                      iters=2 if smoke else 4, shuffle_per_iter=False, seed=0)
    res = trainer.fit(noised.astype(np.float32))
    print("loss by epoch:", [round(float(l), 4) for l in res.losses])
    m = res.metrics
    print("positions that carried loss, first step:",
          int(m["masked_tokens"][0, 0]), "of", 2 * length, "tokens")
    print("expert load of layer 0, first step:",
          m["expert_load"][0, 0, 0].astype(int).tolist())


if __name__ == "__main__":
    main()
