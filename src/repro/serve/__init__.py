from repro.serve.selection import (  # noqa: F401
    Backpressure,
    JobCancelled,
    JobFailed,
    JobInfo,
    ResultCache,
    SelectionRequest,
    SelectionService,
    UnknownJob,
    parse_source_ref,
)
