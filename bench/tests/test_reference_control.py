"""The plain reference against the served path, and the control that has
to fail: the reference computed with bfloat16 float steps."""
import numpy as np
import pytest

import deploy
import net
import reference
import tiny


@pytest.mark.parametrize("name", sorted(tiny.SHRINK))
def test_served_logits_are_the_reference_bit_for_bit(name):
    from repro.serve.vision import MultiModelEngine, VisionEngine

    cfg = tiny.config(name)
    fam = net.family(cfg)
    dep = deploy.build(cfg, fam.blocks(cfg), seed=2**40 + 11)
    qnet = deploy.to_program_qnet(dep, fam.program_netspec(cfg))
    router = MultiModelEngine({"m": VisionEngine(qnet, buckets=(1, 8), name="m")})
    images = np.random.default_rng(0).uniform(
        -1, 1, (19, *net.input_shape(cfg))).astype(np.float32)
    handles = [router.submit("m", x) for x in images]
    results = router.run()
    served = np.stack([results[h].logits for h in handles])
    ref = reference.logits(dep, images)
    assert np.array_equal(served, ref)
    assert len({row.tobytes() for row in ref}) == len(images)  # answers differ


@pytest.mark.parametrize("name", sorted(tiny.SHRINK))
@pytest.mark.parametrize("seed", [3, 987654321, 2**33 + 5])
def test_bfloat16_control_is_not_correct(name, seed):
    cfg = tiny.config(name)
    dep = deploy.build(cfg, net.family(cfg).blocks(cfg), seed=seed)
    images = np.random.default_rng(seed).uniform(
        -1, 1, (16, *net.input_shape(cfg))).astype(np.float32)
    ref = reference.logits(dep, images)
    control = reference.logits(dep, images, low=True)
    assert np.count_nonzero(ref != control) > 0
