SELECT o_orderpriority, COUNT(*) AS order_count
FROM orders
WHERE o_orderdate >= {date_lo} AND o_orderdate < {date_hi}
  AND EXISTS (SELECT * FROM lineitem WHERE l_orderkey = o_orderkey AND l_commitdate < l_receiptdate)
GROUP BY o_orderpriority
ORDER BY o_orderpriority
