import numpy as np
from predictionio_tpu.data.batch import Interactions
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.tools.loadtest import zipf_mandelbrot_weights

def make(n_u, n_i, n, seed=21):
    rng = np.random.default_rng(seed)
    cover = max(n_u, n_i)
    users = np.empty(n, np.int64); items = np.empty(n, np.int64)
    users[:cover] = np.arange(cover) % n_u; items[:cover] = np.arange(cover) % n_i
    users[cover:] = rng.choice(n_u, n - cover, p=zipf_mandelbrot_weights(n_u, s=0.7, q=50.0))
    items[cover:] = rng.choice(n_i, n - cover, p=zipf_mandelbrot_weights(n_i, s=1.1, q=50.0))
    inter = Interactions(user=users.astype(np.int32), item=items.astype(np.int32),
        rating=rng.integers(1, 6, n).astype(np.float32), t=np.zeros(n), user_map=None, item_map=None)
    inter.user_map = BiMap({f"u{i}": i for i in range(n_u)}); inter.item_map = BiMap({f"i{i}": i for i in range(n_i)})
    return inter
