"""How many Mosaic (``tpu_custom_call``) kernels named ``zoo_*`` the loss
and attention routers put into the compiled train step; a count, exact.
The arithmetic is ``chip_smoke.mosaic_kernel_names``'s, kept here."""

import re


def count_mosaic(lowered_text: str) -> int:
    n = 0
    for line in lowered_text.splitlines():
        if "@tpu_custom_call" in line:
            m = re.search(r'kernel_name = "([^"]+)"', line)
            if m and m.group(1).startswith("zoo_"):
                n += 1
    return n


def read(view):
    import jax

    from analytics_zoo_tpu.parallel import mesh as mesh_lib
    model = view["model"]
    loop = model._loop
    x, y = view["batch"]
    bsh = mesh_lib.batch_sharding(loop.mesh)
    lowered = loop._train_step.trace(
        model.params, model.opt_state, model.net_state, jax.random.key(0),
        jax.device_put(x, bsh), jax.device_put(y, bsh)).lower()
    return count_mosaic(lowered.as_text())
