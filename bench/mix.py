"""The keys a traffic mix may set, and the check that holds it to them.

A mix (``bench/traffic/<name>.json``) sets what the generator, the
program and the plain reference all implement, and nothing else:

    federation   the generator's keys (bench/traffic.py)
    engine       client_chunk (any whole number >= 1), rounds_per_chunk (1)
    channel      alpha, xi_scale, mu_c; uplink {"mode": "f32"}
                 (Rayleigh fading, interference on, f32 downlink)
    server       optimizer "adam_ota", lr, beta1, beta2, eps, alpha (a
                 number: the tail index the server assumes)
    why          one line on what the mix stands for

Every client takes part in every round that the mix draws. A key outside
this list, or a value the reference does not implement (another uplink,
optimizer or participation), is an error: a mix runs what it names or it
does not run.
"""

from __future__ import annotations

NUMBER = (int, float)

KEYS = {
    "federation": {"population": int, "clients_per_round": int, "batch": int,
                   "seq": int, "domains": int, "dirichlet": NUMBER,
                   "zipf": NUMBER, "follow": NUMBER},
    "engine": {"client_chunk": int, "rounds_per_chunk": int},
    "channel": {"alpha": NUMBER, "xi_scale": NUMBER, "mu_c": NUMBER,
                "uplink": dict},
    "server": {"optimizer": str, "lr": NUMBER, "beta1": NUMBER,
               "beta2": NUMBER, "eps": NUMBER, "alpha": NUMBER},
}
REQUIRED = {
    "federation": {"population", "clients_per_round", "batch", "seq",
                   "domains", "dirichlet", "zipf"},
    "engine": {"client_chunk", "rounds_per_chunk"},
    "channel": {"alpha", "xi_scale"},
    "server": {"optimizer", "lr", "beta2"},
}
ONLY = {("engine", "rounds_per_chunk"): (1,),
        ("channel", "uplink"): ({"mode": "f32"},),
        ("server", "optimizer"): ("adam_ota",)}


def check(mix: dict) -> dict:
    """``mix`` itself, once every key and value is one that the program
    and the reference implement; ValueError otherwise."""
    extra = set(mix) - set(KEYS) - {"why"}
    if extra:
        raise ValueError(f"mix sets unknown blocks {sorted(extra)}")
    for block, keys in KEYS.items():
        got = mix.get(block)
        if not isinstance(got, dict):
            raise ValueError(f"mix has no {block!r} block")
        unknown = set(got) - set(keys)
        if unknown:
            raise ValueError(f"mix {block!r} sets {sorted(unknown)}, which "
                             f"the benchmark does not implement; it "
                             f"implements {sorted(keys)}")
        missing = REQUIRED[block] - set(got)
        if missing:
            raise ValueError(f"mix {block!r} lacks {sorted(missing)}")
        for key, value in got.items():
            kind = keys[key]
            if isinstance(value, bool) or not isinstance(value, kind):
                raise ValueError(f"mix {block}.{key} = {value!r} is not a "
                                 f"{getattr(kind, '__name__', 'number')}")
            only = ONLY.get((block, key))
            if only is not None and value not in only:
                raise ValueError(f"mix {block}.{key} = {value!r}: only "
                                 f"{list(only)} is implemented")
    if mix["engine"]["client_chunk"] < 1:
        raise ValueError("mix engine.client_chunk must be at least 1")
    return mix
