"""Plain reference of memcached item sizes: key plus value bytes.

Written from the published fits and from nothing of the program under
test. Atikoglu et al., "Workload Analysis of a Large-Scale Key-Value
Store" (SIGMETRICS 2012), fit the ETC pool's key sizes with a
generalized extreme value law and its value sizes with a generalized
Pareto law; mutilate draws them the same way. An item is
``round(key) + round(value)`` bytes, clipped to ``[1, max_len]``, each
law sampled by its quantile function:

- key ``mu_k + sigma_k * ((-ln u) ** -xi_k - 1) / xi_k``;
- value ``mu_v + sigma_v * ((1 - u') ** -xi_v - 1) / xi_v``;

where ``u`` are the first N uniforms of ``default_rng(length_seed ^
0x5EED1E)`` and ``u'`` the next N. That draw is part of the exact
guarantee, as the trace's is (``reference_mc``).
"""

from __future__ import annotations

import numpy as np

# (mu, sigma, xi): the ETC fits, with mutilate's 15-byte least value.
ETC_KEY = (30.7984, 8.20449, 0.078688)
ETC_VALUE = (15.0, 214.476, 0.348238)


def item_lengths(spec: dict, n_objects: int, length_seed: int) -> np.ndarray:
    """(N,) item bytes of an ``item`` length specification in its JSON
    form (``key_gev``, ``value_gpd``, ``max_len``)."""
    mk, sk, xk = spec.get("key_gev", ETC_KEY)
    mv, sv, xv = spec.get("value_gpd", ETC_VALUE)
    rng = np.random.default_rng(length_seed ^ 0x5EED1E)
    u_key = rng.random(n_objects)
    u_value = rng.random(n_objects)
    with np.errstate(divide="ignore"):  # u = 0 gives the least key
        key = mk + sk * ((-np.log(u_key)) ** -xk - 1) / xk
    value = mv + sv * ((1 - u_value) ** -xv - 1) / xv
    size = np.round(key) + np.round(value)
    return np.clip(size, 1, spec["max_len"]).astype(np.int64)
