from repro.data.binning import (  # noqa: F401
    BinnedSource,
    QuantileBinner,
    QuantileSketch,
    fit_binned,
)
from repro.data.synthetic import corral_dataset  # noqa: F401
from repro.data.sources import (  # noqa: F401
    ArraySource,
    CSVSource,
    CorralSource,
    DataSource,
    NpySource,
    SourceStats,
    as_source,
)
