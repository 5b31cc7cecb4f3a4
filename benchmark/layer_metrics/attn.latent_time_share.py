"""The latent-attention layers' share of the device's busy time: traced
time of the operations under the scopes ``zoo_mla.*`` (q_latent, kv_latent,
expand, rope, attend, out) and of the flash kernels (which the layer calls
under ``zoo_mla.attend``) over the traced window's busy time. Reads nothing
where the step has no such scope."""

from benchmark.lib import scopes

FLASH_KERNELS = ("zoo_flash",)


def read(view):
    tr = view["trace"]
    if tr is None or tr["busy_s"] <= 0:
        return None
    spent = scopes.scope_seconds(tr, scopes.step_text(view), "zoo_mla.",
                                 FLASH_KERNELS)
    return 100.0 * spent / tr["busy_s"] if spent > 0 else None
