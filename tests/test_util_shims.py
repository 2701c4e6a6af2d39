"""Joblib backend and usage-stats shims (reference:
python/ray/util/joblib/ and python/ray/_private/usage/usage_lib.py)."""

import math

import ray_tpu


RAY_START = dict(num_cpus=2)


def test_joblib_backend(ray_start):
    import joblib
    from joblib import Parallel, delayed

    from ray_tpu.util.joblib_backend import register_ray_tpu
    register_ray_tpu()
    with joblib.parallel_backend("ray_tpu", n_jobs=2):
        out = Parallel()(delayed(math.factorial)(i) for i in range(8))
    assert out == [math.factorial(i) for i in range(8)]


def test_usage_stats(ray_start):
    from ray_tpu._private import usage_stats
    usage_stats.record_library_usage("train")
    usage_stats.record_extra_usage_tag("topology", "v4-8")
    rep = usage_stats.usage_report()
    assert rep.get("library_train") == "1"
    assert rep.get("topology") == "v4-8"
