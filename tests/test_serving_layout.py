"""Weight-stationary serving layout + engine slot-cache helpers."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import _drop_axes


def test_drop_axes_variants():
    assert _drop_axes(P("data", "model"), {"data"}) == P(None, "model")
    assert _drop_axes(P(("pod", "data"), None), {"data", "pod"}) == \
        P(None, None)
    assert _drop_axes(P(("pod", "model"), "data"), {"pod", "data"}) == \
        P("model", None)
    assert _drop_axes(P("model", None, "data"), {"data"}) == \
        P("model", None, None)


def test_serving_param_shardings_drop_fsdp():
    from repro import configs
    from repro.launch.mesh import make_mesh
    from repro.models import model as MDL
    from repro.parallel import sharding as SH

    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = configs.get_smoke("qwen2.5-3b")
    shapes = MDL.param_shapes(cfg)
    sh_serve = SH.param_shardings(shapes, mesh, serving=True)

    def specs(tree):
        return [s.spec for s in jax.tree_util.tree_leaves(
            tree, is_leaf=lambda x: hasattr(x, "spec"))]
    for sp in specs(sh_serve):
        for entry in sp:
            axes = entry if isinstance(entry, tuple) else (entry,)
            assert "data" not in axes and "pod" not in axes


def test_cache_slot_roundtrip():
    from repro import configs
    from repro.models import model as MDL
    cfg = configs.get_smoke("recurrentgemma-2b")   # mixed kv + rnn caches
    cache = MDL.init_cache(cfg, 3, 16)
    # write a distinguishable value into slot 1, read it back
    sub = MDL.cache_take_slot(cache, 1)
    sub = jax.tree_util.tree_map(lambda t: jnp.ones_like(t), sub)
    cache2 = MDL.cache_put_slot(cache, 1, sub)
    back = MDL.cache_take_slot(cache2, 1)
    for leaf in jax.tree_util.tree_leaves(back):
        np.testing.assert_allclose(np.asarray(leaf, np.float32), 1.0)
    other = MDL.cache_take_slot(cache2, 0)
    for leaf in jax.tree_util.tree_leaves(other):
        np.testing.assert_allclose(np.asarray(leaf, np.float32), 0.0)
