"""Every whole chain of `length` lanes has one payer: each lane's debit
account becomes the chain's first lane's, and a payee (credit) that then
equals the payer moves to the next plain account. No rng: a pure function
of the batch. Lanes past the last whole chain are left as drawn."""
import numpy as np


def apply(stream, mod, arr, base):
    length = int(mod["length"])
    whole = len(arr) // length * length
    payer = np.repeat(arr["debit_account_id_lo"][:whole:length], length)
    credit = arr["credit_account_id_lo"][:whole]
    arr["debit_account_id_lo"][:whole] = payer
    arr["credit_account_id_lo"][:whole] = np.where(
        credit == payer, payer % np.uint64(stream.plain_accounts) + np.uint64(1),
        credit)
    return arr
