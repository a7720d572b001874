"""Golden star/blockfade campaign, shared by tests/test_fl.py and
tests/test_topology.py: smoke fedsllm-100m (LoRA rank 4 / alpha 8), K=6, EB,
eta=0.5, cohort 4, deadline = 0.7-quantile of the constructor timing, 3
resampled rounds.

The simulated times come from numpy and are pinned at rtol 1e-12.  The
float32 losses come from XLA's CPU backend under jax 0.9.0, whose PRNG
(``jax_threefry_partitionable`` on, the default since jax 0.5) draws other
initial weights and tokens than the jax 0.4 capture did: with that flag off,
rounds 0 and 1 reproduce the jax 0.4 losses to every digit and round 2 is
5e-5 off, so the rest is float32 rounding across XLA versions.
"""

GOLDEN_DEADLINE = 110.61189496631023
GOLDEN_ROUND_TIMES = (110.61189496631023, 110.61189496631023,
                      104.78746742360255)
GOLDEN_TOTAL_TIME = 326.01125735622304
GOLDEN_LOSSES = (5.548229217529297, 5.549412727355957, 5.578916072845459)
