"""Baseline config #3: CLIP ViT-L image embedding fan-out across N×v5e-1
task-queue workers (queue-depth autoscaling from zero).

    # producer side:
    python3 -c "
    from examples.x03_clip_fanout import embed_image
    handles = [embed_image.put(url) for url in urls]
    vectors = [h.result(timeout=300) for h in handles]"
"""

from tpu9 import QueueDepthAutoscaler, task_queue

_state = {}


def _model():
    if "apply" not in _state:
        import jax
        from tpu9.models.clip_vit import (CLIP_VIT_L14, clip_vision_forward,
                                          init_clip_vision)
        params = init_clip_vision(jax.random.PRNGKey(0), CLIP_VIT_L14)
        _state["apply"] = jax.jit(
            lambda imgs: clip_vision_forward(params, imgs, CLIP_VIT_L14))
    return _state["apply"]


@task_queue(tpu="v5e-1", cpu=2, memory="16Gi",
            autoscaler=QueueDepthAutoscaler(max_containers=16,
                                            tasks_per_container=4))
def embed_image(url: str = "", pixels=None):
    """One task per image; the engine batches at the XLA level via jit."""
    import jax.numpy as jnp
    import numpy as np

    if pixels is None:
        # image fetch/decode left to the deployment's image (PIL etc.);
        # callers may pass raw pixel arrays directly
        raise ValueError("pass pixels=[H][W][3] floats (0..1)")
    img = jnp.asarray(np.array(pixels, dtype=np.float32))[None]
    return {"embedding": _model()(img)[0].tolist()}
