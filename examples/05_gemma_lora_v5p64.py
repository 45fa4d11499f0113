"""Baseline config #5: Gemma-7B LoRA fine-tune on a multi-host v5p-64 slice
via @function — 16 gang-scheduled containers, one per host, joined into a
single jax.distributed job with FSDP over ICI.

    from examples.x05_gemma_lora_v5p64 import finetune
    task = finetune.submit(dataset_path="/data/corpus.jsonl", steps=1000)
    print(task.result(timeout=7200))
"""

from tpu9 import Volume, function


@function(tpu="v5p-64", cpu=32, memory="200Gi", timeout=4 * 3600,
          volumes=[Volume(name="gemma-7b", mount_path="/models/gemma-7b"),
                   Volume(name="datasets", mount_path="/data")])
def finetune(dataset_path: str = "", steps: int = 100, lr: float = 1e-4,
             lora_rank: int = 16):
    # 1) join the slice-wide jax.distributed job (the worker injected
    #    TPU9_GANG_RANK/SIZE + JAX_COORDINATOR_ADDRESS for this gang)
    from tpu9.parallel.distributed import initialize_multihost
    info = initialize_multihost()

    import jax
    import jax.numpy as jnp
    import optax

    from tpu9.models import init_decoder, lora
    from tpu9.models.gemma import GEMMA_PRESETS
    from tpu9.parallel import decoder_param_specs, fsdp_specs, make_mesh, shard_params
    from tpu9.train import build_lora_train_step

    cfg = GEMMA_PRESETS["gemma-7b"]
    n = jax.device_count()               # 64 chips across the 16 hosts
    mesh = make_mesh(dp=1, fsdp=n // 4, sp=1, tp=4)

    base = init_decoder(jax.random.PRNGKey(0), cfg)     # volume loader IRL
    base = shard_params(base, mesh, decoder_param_specs(base))
    adapters = lora.init_lora(jax.random.PRNGKey(1), base, rank=lora_rank)
    adapters = shard_params(adapters, mesh, fsdp_specs(adapters, min_size=1))

    opt = optax.adamw(lr)
    opt_state = opt.init(adapters)
    step = build_lora_train_step(cfg, opt, scale=lora.lora_scale(lora_rank))

    losses = []
    with jax.set_mesh(mesh):
        for i in range(steps):
            # dataset iterator elided: per-host shards of dataset_path
            tokens = jax.random.randint(jax.random.PRNGKey(i), (8, 512), 0,
                                        cfg.vocab_size)
            adapters, opt_state, metrics = step(adapters, opt_state, base,
                                                tokens)
            if i % 10 == 0:
                losses.append(float(metrics["loss"]))

    if info is None or info.is_coordinator:
        from tpu9.runner import ckpt
        ckpt.save_params(adapters, name="lora_adapters")
    return {"final_loss": losses[-1] if losses else None,
            "loss_curve": losses, "ranks": info.size if info else 1}
