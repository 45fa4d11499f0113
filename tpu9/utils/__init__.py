from .platform import force_cpu, device_kind, on_tpu
from .paths import (compile_cache_dir, native_binary, repo_root,
                    validate_path_part)
from .aio import (cancellable_wait, event_wait, queue_get, reap, spawn,
                  bg_task_count)

__all__ = ["force_cpu", "device_kind", "on_tpu", "validate_path_part",
           "compile_cache_dir", "native_binary", "repo_root",
           "cancellable_wait", "event_wait", "queue_get", "reap", "spawn",
           "bg_task_count"]
