"""Test double: with BENCH_TEST_BREAK_TOKENS set, every fifth token is
altered where the scheduler produces it (`_append_token`). Put on
PYTHONPATH by tests/bench/test_bench_rehearsal.py only; the benchmark
never loads it."""

import importlib.abc
import importlib.util
import os
import sys

TARGET = "dynamo_tpu.engine.scheduler"


class _BreakTokens(importlib.abc.MetaPathFinder):
    def find_spec(self, name, path, target=None):
        if name != TARGET:
            return None
        sys.meta_path.remove(self)
        spec = importlib.util.find_spec(name)
        run = spec.loader.exec_module

        def exec_module(module):
            run(module)
            inner = module.InferenceScheduler._append_token

            def broken(self, seq, token, *args, **kwargs):
                if len(seq.generated) % 5 == 4:
                    token = (int(token) + 1) % 500
                return inner(self, seq, token, *args, **kwargs)

            module.InferenceScheduler._append_token = broken

        spec.loader.exec_module = exec_module
        return spec


if os.environ.get("BENCH_TEST_BREAK_TOKENS"):
    sys.meta_path.insert(0, _BreakTokens())
