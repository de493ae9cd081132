"""Federated LoRA fine-tuning of a Llama-style LM with in-learner sharding.

The BASELINE.md north-star shape (Llama-LoRA federation with in-learner
pjit sharding; the reference has no transformer or TP story at all —
SURVEY.md §2.3): each learner trains ONLY its LoRA adapters
(``trainable_regex="lora_"``) with params sharded over a ``dp × tp`` mesh
per :data:`TRANSFORMER_RULES` (column/row-parallel attention + MLP — XLA
inserts the all-reduces), and FedAvg merges the rounds.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/llama_lora.py --dim 64 --rounds 2

``--hybrid`` swaps the model for the attention / state-space hybrid
(``JambaLite``: Mamba mixers with one attention block a period, adapters on
``in_proj``/``out_proj`` and ``wq``/``wv``); the federation, the shipped
subset and the decode at the end are the same code. ``--latent`` swaps it
for the latent-attention decoder with a share of its routed experts
(``MlaMoeLite``: a dense block, then blocks whose router chooses 4 of 16
experts of which this model holds 4, beside a shared expert; adapters on
the four latent projections; the frozen base held in bfloat16).
``--shortcut`` swaps it for the shortcut-connected decoder (``ScMoeLite``:
two latent-attention sublayers and two dense FFNs a layer beside one
routed layer whose softmax router chooses 4 of 16 routed and 8
zero-computation experts, 4 routed ones held; two latent caches a layer in
the decode).
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> int:
    parser = argparse.ArgumentParser("federated llama-lora")
    parser.add_argument("--learners", type=int, default=2)
    parser.add_argument("--rounds", type=int, default=2)
    parser.add_argument("--dim", type=int, default=64)
    parser.add_argument("--depth", type=int, default=2)
    parser.add_argument("--heads", type=int, default=4)
    parser.add_argument("--vocab", type=int, default=256)
    parser.add_argument("--seq-len", type=int, default=32)
    parser.add_argument("--lora-rank", type=int, default=8)
    parser.add_argument("--hybrid", action="store_true",
                        help="JambaLite (Mamba mixers, an attention block "
                             "every second layer) in LlamaLite's place")
    parser.add_argument("--latent", action="store_true",
                        help="MlaMoeLite (latent attention, routed experts "
                             "of which a share is held, a bfloat16 base) "
                             "in LlamaLite's place")
    parser.add_argument("--shortcut", action="store_true",
                        help="ScMoeLite (two latent-attention sublayers and "
                             "two dense FFNs a layer beside a routed layer "
                             "with zero-computation experts, a bfloat16 "
                             "base) in LlamaLite's place")
    parser.add_argument("--scan-chunk", type=int, default=1,
                        help="fuse this many local steps into one compiled "
                             "scan program (dispatch amortization on TPU)")
    parser.add_argument("--dp", type=int, default=2)
    parser.add_argument("--tp", type=int, default=0,
                        help="0 = absorb remaining devices")
    args = parser.parse_args()

    from metisfl_tpu.platform import enter_process
    enter_process()

    import numpy as np

    from metisfl_tpu.comm.messages import TrainParams
    from metisfl_tpu.config import (AggregationConfig, EvalConfig,
                                    FederationConfig, TerminationConfig)
    from metisfl_tpu.driver import InProcessFederation
    from metisfl_tpu.models import ArrayDataset, FlaxModelOps
    from metisfl_tpu.models.zoo import (TRANSFORMER_RULES, JambaLite,
                                        LlamaLite, MlaMoeLite, ScMoeLite)
    from metisfl_tpu.parallel.mesh import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(("dp", "tp"), (args.dp, args.tp)))
    print(f"mesh: {dict(mesh.shape)}")

    rng = np.random.default_rng(0)

    def lm_shard(seed):
        # synthetic 'language': order-2 markov tokens, learnable offline
        trans = rng.dirichlet(np.ones(args.vocab) * 0.05,
                              size=args.vocab)
        toks = np.zeros((200, args.seq_len + 1), np.int32)
        state = rng.integers(0, args.vocab, 200)
        for t in range(args.seq_len + 1):
            toks[:, t] = state
            nxt = [rng.choice(args.vocab, p=trans[s]) for s in state]
            state = np.asarray(nxt)
        return ArrayDataset(toks[:, :-1], toks[:, 1:], seed=seed)

    if args.hybrid:
        module = JambaLite(vocab_size=args.vocab, dim=args.dim,
                           depth=args.depth, heads=args.heads, kv_heads=1,
                           attn_period=2, attn_offset=1, d_state=8,
                           lora_rank=args.lora_rank)
    elif args.latent:
        import jax.numpy as jnp
        module = MlaMoeLite(vocab_size=args.vocab, dim=args.dim,
                            depth=max(2, args.depth), heads=args.heads,
                            q_rank=args.dim // 4, kv_rank=args.dim // 8,
                            nope_dim=16, rope_dim=8, v_dim=16,
                            moe_hidden=args.dim // 2, num_experts=16,
                            top_k=4, experts_count=4, routed_scale=2.0,
                            rope_factor=4.0, rope_original_max=16,
                            rope_mscale_all_dim=1.0,
                            lora_rank=args.lora_rank, dtype=jnp.bfloat16,
                            param_dtype=jnp.bfloat16)
    elif args.shortcut:
        import jax.numpy as jnp
        module = ScMoeLite(vocab_size=args.vocab, dim=args.dim,
                           depth=max(2, args.depth // 2), heads=args.heads,
                           q_rank=args.dim // 4, kv_rank=args.dim // 8,
                           nope_dim=16, rope_dim=8, v_dim=16,
                           moe_hidden=args.dim // 2, num_experts=16,
                           zero_experts=8, top_k=4, experts_count=4,
                           routed_scale=6.0, lora_rank=args.lora_rank,
                           dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    else:
        module = LlamaLite(vocab_size=args.vocab, dim=args.dim,
                           depth=args.depth, heads=args.heads,
                           lora_rank=args.lora_rank)
    config = FederationConfig(
        aggregation=AggregationConfig(scaler="participants"),
        # ship-only-trainable: just the LoRA adapters cross the wire, and
        # the controller holds only adapter state — an 8B frozen base never
        # leaves the learners (TrainParams.ship_tensor_regex)
        train=TrainParams(batch_size=16, local_steps=4, learning_rate=0.01,
                          optimizer="adam", scan_chunk=args.scan_chunk,
                          ship_tensor_regex="lora_"),
        eval=EvalConfig(every_n_rounds=0),
        termination=TerminationConfig(federation_rounds=args.rounds),
    )
    fed = InProcessFederation(config)
    sample = np.zeros((2, args.seq_len), np.int32)
    template = None
    for i in range(args.learners):
        ops = FlaxModelOps(module, sample, rng_seed=0, mesh=mesh,
                           partition_rules=TRANSFORMER_RULES,
                           trainable_regex="lora_",
                           variables=template)  # learner 0 inits; rest reuse
        if template is None:
            template = ops.get_variables()
        fed.add_learner(ops, lm_shard(i))
    fed.seed_model(template)
    fed.start()
    ok = fed.wait_for_rounds(args.rounds, timeout_s=900)
    stats = fed.statistics()
    fed.shutdown()
    print(f"completed {stats['global_iteration']} rounds"
          + ("" if ok else " (timeout)"))
    import jax
    n_total = sum(int(np.size(l)) for l in jax.tree.leaves(template))
    n_lora = sum(
        int(np.size(l)) for p, l in
        jax.tree_util.tree_flatten_with_path(template)[0]
        if "lora_" in "/".join(str(k) for k in p))
    print(f"params: {n_total} total, {n_lora} trainable LoRA "
          f"({100 * n_lora / n_total:.1f}%)")

    # KV-cache decode on the federated model (models/generate.py): greedy
    # continuation of a prompt, one jitted program for the whole sequence.
    # The community blob carries ONLY the adapters; overlay them on the
    # (frozen, shared) base exactly like a learner's backfill.
    from metisfl_tpu.tensor.pytree import (ModelBlob,
                                           named_tensors_to_pytree,
                                           pytree_to_named_tensors)
    blob = fed.controller.community_model_bytes()
    if blob:
        adapters = dict(ModelBlob.from_bytes(blob).tensors)
        print(f"community blob: {sum(a.nbytes for a in adapters.values())} "
              f"B of adapters (full model would be "
              f"{sum(np.asarray(l).nbytes for l in jax.tree.leaves(template))} B)")
        merged = [(n, adapters.get(n, a))
                  for n, a in pytree_to_named_tensors(template)]
        final = named_tensors_to_pytree(merged, template)
    else:
        final = template
    gen_ops = FlaxModelOps(module, sample, variables=final)
    prompt = np.arange(1, 9, dtype=np.int32)[None, :]
    tokens = gen_ops.generate(prompt, max_new_tokens=8)
    print(f"greedy continuation of {prompt[0].tolist()}: "
          f"{tokens[0].tolist()}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
