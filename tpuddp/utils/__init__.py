"""Utility subsystems: shape-key batching (``batching``) and the persistent
compile cache (``compile_cache``). Observability is the
``tpuddp.observability`` package."""
