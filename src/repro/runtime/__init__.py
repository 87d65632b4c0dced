from .fault import InjectedFailure, TrainDriver
from .platform import enable_compile_cache, pin_to_cpu

__all__ = ["InjectedFailure", "TrainDriver", "enable_compile_cache", "pin_to_cpu"]
