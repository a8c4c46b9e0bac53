"""Launchers: the profiler's drivers (``profile_run``, ``serve_profiler``,
``serve_fleet``) and the persistent compile cache they share."""
