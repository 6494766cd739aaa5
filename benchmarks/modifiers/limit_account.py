"""The limit account (debits_must_not_exceed_credits) is credited, then
debited beyond its credits: the debit must fail exceeds_credits, in commit order."""


def apply(stream, mod, arr, base):
    n = len(arr)
    lanes = (10 % n, n // 2)
    arr["credit_account_id_lo"][lanes[0]] = stream.limit_id
    arr["amount_lo"][lanes[0]] = int(mod["credit"])
    arr["debit_account_id_lo"][lanes[1]] = stream.limit_id
    arr["amount_lo"][lanes[1]] = int(mod["debit"])
    if int(arr["debit_account_id_lo"][lanes[0]]) == stream.limit_id:
        arr["debit_account_id_lo"][lanes[0]] = 1
    if int(arr["credit_account_id_lo"][lanes[1]]) == stream.limit_id:
        arr["credit_account_id_lo"][lanes[1]] = 1
    return arr
