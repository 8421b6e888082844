"""Fine-tune a HuggingFace checkpoint under the framework — the
switching path for reference users.

Usage::

    python examples/jax/finetune_hf.py [--family llama|gpt2] [--steps 20]

The reference framework wraps torch training in place, so its users'
weights live in torch/HF checkpoints (reference analog: torch adapter +
``broadcast_parameters``, SURVEY §2.4). This example is the full
migration loop on a toy model:

1. build (or in real use, ``from_pretrained``-load) an HF model,
2. ``from_hf_llama`` / ``from_hf_gpt2`` it into the GPT family,
3. fine-tune with ``make_gpt_train_step(init_params=...)`` on a dp×tp
   mesh with onebit-compressed gradient aggregation,
4. sample from the tuned weights with the KV-cache decoder,
5. ``to_hf_llama`` / ``to_hf_gpt2`` the result back into a fresh HF
   model via ``load_state_dict``.

With network access and real weights the only change is step 1:
``transformers.LlamaForCausalLM.from_pretrained(...)`` — the bridge
maps rope/GQA/SwiGLU/RMSNorm/untied-readout automatically and rejects
option sets it cannot reproduce exactly (rope_scaling, decoupled
head_dim) instead of importing them misnumbered.
"""

import argparse

import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--family", choices=("llama", "gpt2"), default="llama")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--dp", type=int, default=None,
                    help="data-parallel ways (default: all devices)")
    ap.add_argument("--tp", type=int, default=1)
    args = ap.parse_args()

    import torch
    import transformers

    import jax
    import jax.numpy as jnp
    import optax

    from byteps_tpu.common.compile_cache import enable_compile_cache
    from byteps_tpu.models.generate import make_generate_fn
    from byteps_tpu.models.import_hf import (
        from_hf_gpt2, from_hf_llama, to_hf_gpt2, to_hf_llama)
    from byteps_tpu.models.train import make_gpt_train_step
    from byteps_tpu.parallel import MeshAxes, make_mesh

    enable_compile_cache()

    # 1. the "existing" HF model (toy size; from_pretrained in real use)
    torch.manual_seed(0)
    if args.family == "llama":
        hf_cfg = transformers.LlamaConfig(
            vocab_size=512, hidden_size=128, intermediate_size=256,
            num_hidden_layers=4, num_attention_heads=8,
            num_key_value_heads=4, max_position_embeddings=128)
        hf_model = transformers.LlamaForCausalLM(hf_cfg).eval()
        cfg, params = from_hf_llama(hf_model)
    else:
        hf_cfg = transformers.GPT2Config(
            vocab_size=512, n_positions=128, n_embd=128, n_layer=4,
            n_head=8)
        hf_model = transformers.GPT2LMHeadModel(hf_cfg).eval()
        cfg, params = from_hf_gpt2(hf_model)
    print(f"imported {args.family}: {cfg.n_layers}L d{cfg.d_model} "
          f"norm={cfg.norm} mlp={cfg.mlp} pos={cfg.pos_embedding}")

    # 2. fine-tune under compressed dp aggregation (× optional tp)
    n_dev = len(jax.devices())
    dp = args.dp if args.dp is not None else max(1, n_dev // args.tp)
    mesh = make_mesh(MeshAxes(dp=dp, tp=args.tp))
    step, p, o, batch_sharding = make_gpt_train_step(
        cfg, mesh, optax.adamw(3e-4),
        compression_params={"compressor": "onebit", "ef": True},
        init_params=params)

    rng = np.random.RandomState(0)
    B, S = 2 * dp, 64
    for i in range(args.steps):
        toks = rng.randint(0, cfg.vocab_size, (B, S))
        tgts = np.roll(toks, -1, axis=1)
        loss, p, o = step(p, o,
                          jax.device_put(jnp.asarray(toks), batch_sharding),
                          jax.device_put(jnp.asarray(tgts), batch_sharding))
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:3d}  loss {float(loss):.4f}")

    tuned = jax.tree_util.tree_map(np.asarray, jax.device_get(p))

    # 3. sample from the tuned weights (KV-cache decode)
    gen = make_generate_fn(cfg, max_new=16)
    prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 8)))
    out = gen(jax.tree_util.tree_map(jnp.asarray, tuned), prompt,
              jax.random.PRNGKey(0), temperature=0.8)
    print("sampled:", np.asarray(out)[0, 8:].tolist())

    # 4. export back to HF
    to_hf = to_hf_llama if args.family == "llama" else to_hf_gpt2
    sd = {k: torch.as_tensor(np.array(v)) for k, v in
          to_hf(tuned, cfg).items()}
    fresh = type(hf_model)(hf_cfg).eval()
    missing, unexpected = fresh.load_state_dict(sd, strict=False)
    assert not unexpected, unexpected
    # a partial export would leave `fresh` half-initialized — the only
    # tolerable misses are non-persistent buffers (e.g. GPT-2's causal
    # `attn.bias` masks), mirroring tests/test_import_hf.py
    persistent_missing = [k for k in missing
                          if not k.endswith((".attn.bias",
                                             ".attn.masked_bias"))]
    assert not persistent_missing, persistent_missing
    print("exported back to HF:", type(fresh).__name__,
          f"({sum(v.numel() for v in sd.values())} params)")


if __name__ == "__main__":
    main()
