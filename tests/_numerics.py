"""Tolerances shared by the decode-engine tests.

Two DIFFERENT compiled programs that compute the same stream — a speculative
verify chunk and the decode chunk, a resumed session and a never-evicted one,
a suffix prefill and a full prefill — agree token for token, but XLA orders
each program's float32 reductions as it likes, so their log-probabilities
agree to a few units in the last place, not bit for bit. (The same program
run twice on the same inputs IS bit-identical, and tests of that keep `==`.)
"""

import numpy as np

# 16 ulp of float32 at the value, and an absolute floor for values near 0
LOGPROB_RTOL = 2e-6
LOGPROB_ATOL = 1e-6


def assert_logprobs_close(got, want, msg="") -> None:
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(want, np.float32),
        rtol=LOGPROB_RTOL,
        atol=LOGPROB_ATOL,
        err_msg=str(msg),
    )
