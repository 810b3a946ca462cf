from pypulsar_tpu.io import sigproc  # noqa: F401
from pypulsar_tpu.io.filterbank import FilterbankFile, write_filterbank  # noqa: F401
from pypulsar_tpu.io.infodata import InfoData  # noqa: F401
from pypulsar_tpu.io.psrfits import (  # noqa: F401
    PsrfitsFile,
    SpectraInfo,
    is_PSRFITS,
    DATEOBS_to_MJD,
    write_psrfits,
    unpack_4bit,
)
from pypulsar_tpu.io.opener import open_reader  # noqa: F401
from pypulsar_tpu.io.rfimask import RfifindMask, write_mask  # noqa: F401
from pypulsar_tpu.io.parfile import PsrPar, psr_par, write_par  # noqa: F401
from pypulsar_tpu.io.prestopfd import PfdFile, make_pfd, fft_rotate  # noqa: F401
from pypulsar_tpu.io.accelcands import (  # noqa: F401
    Candidate,
    DMHit,
    AccelcandsError,
    parse_candlist,
    write_candlist,
)
from pypulsar_tpu.io.fbobs import FilterbankObs  # noqa: F401
from pypulsar_tpu.io.wapp import WappFile  # noqa: F401
from pypulsar_tpu.io.datafile import autogen_dataobj, Data  # noqa: F401
